"""Host time the engine spends per dispatch outside the device: the
program's ``engine.tick`` spans in the traced window less the
``engine.device`` spans inside them (program calls with the read of
their result, state reads and uploads), over the count of prefill-chunk
and decode-window dispatches (``engine.prefill_chunk``,
``engine.decode_window``)."""
from chipbench.trace import Trace, latest_xplane

SPANS = {"engine.tick", "engine.device", "engine.prefill_chunk",
         "engine.decode_window"}


def outermost(spans, name):
    """(start, end) of the spans of ``name`` that lie inside no other of
    the same name (a region wrapped under the program's own name by the
    benchmark shows twice), merged where they overlap."""
    out = []
    for s, e in sorted((s, e) for n, s, e in spans if n == name):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def read(ctx):
    if ctx.get("trace_dir") is None:
        return None
    tr = Trace.from_file(latest_xplane(ctx["trace_dir"]), span_names=SPANS)
    return host_ms_per_dispatch(tr.spans)


def host_ms_per_dispatch(spans):
    """From (name, start_ns, end_ns) host spans; None without a tick or a
    dispatch."""
    dispatches = (len(outermost(spans, "engine.prefill_chunk"))
                  + len(outermost(spans, "engine.decode_window")))
    ticks = outermost(spans, "engine.tick")
    if not dispatches or not ticks:
        return None
    device = outermost(spans, "engine.device")
    host_ns, j = 0, 0
    for s, e in ticks:
        while j < len(device) and device[j][1] <= s:
            j += 1
        k, inside = j, 0
        while k < len(device) and device[k][0] < e:
            inside += min(e, device[k][1]) - max(s, device[k][0])
            k += 1
        host_ns += (e - s) - inside
    return host_ns / dispatches / 1e6
