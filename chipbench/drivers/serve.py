"""Driver of ``serve`` configurations: a split LLM served by the program's
``BatchedEngine`` behind its ``FrontDoorServer``, loaded by the clients of
``chipbench/loadgen.py`` in a CPU-only child process.

One run:

1. set-up — weights made on the device from the seed in one jitted call,
   the codec's fixed keys from the link's key seed; the engine built; one direct request through it, so
   every program the window drives is compiled (or read from the
   persistent cache) before the front door opens; the server started and
   the load generator's first wave served;
2. the window — ``--seconds`` of traffic, measured from the clients' side
   (with ``--trace 1`` the profiler records its first ``TRACE_SECONDS``);
3. the check — once the window has closed and in-flight requests have
   finished, the engine is freed and the plain reference
   (``chipbench/references/``) recomputes a sample of the finished
   requests from the seed, with the same C3-SL grouping the engine used
   (read from its dispatches by :class:`ScheduleRecorder`), and each served
   token's logit is compared with the reference's best.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import json
import os
import sys
import time
import warnings

import numpy as np

from chipbench import harness, weights
from chipbench.traffic import generator as gen

TRACE_SECONDS = 10.0
SAMPLE_REQUESTS = 32
OCCUPANCY_PERIOD_S = 0.005
# span names the benchmark writes around its calls into the engine
SPANS = ("engine.prefill_chunk", "engine.decode_window", "engine.boundary",
         "frontdoor.deliver")


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def model_config(conf: dict):
    """The program's ModelConfig from the configuration's HF keys."""
    from repro.configs.base import ModelConfig
    m = conf["model"]
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m["head_dim"],
        rope_theta=m["rope_theta"], block_pattern=(("attn", "mlp"),),
        source=conf["source"])


def program_params(W: dict, m: dict) -> dict:
    """The program's parameter tree, built from the canonical tensors."""
    import jax.numpy as jnp
    L = m["num_hidden_layers"]

    def stack(name):
        return jnp.stack([W[f"layers.{i}.{name}"] for i in range(L)])

    return {"embed": W["embed"],
            "stack": {"l0_0_attn": {"norm": {"scale": stack("attn_norm")},
                                    "w_q": stack("wq"), "w_k": stack("wk"),
                                    "w_v": stack("wv"), "w_o": stack("wo")},
                      "l0_1_mlp": {"norm": {"scale": stack("mlp_norm")},
                                   "w_gate": stack("w_gate"),
                                   "w_up": stack("w_up"),
                                   "w_down": stack("w_down")}},
            "final_norm": {"scale": W["final_norm"]},
            "head": W["head"]}


def make_params(conf: dict, seed: int):
    """Weights on the device, in one jitted call from the seed; the tree
    is checked against the program's own parameter shapes."""
    import jax
    from repro.models import lm as lm_lib
    m = conf["model"]
    cfg = model_config(conf)
    want = jax.eval_shape(lambda k: lm_lib.init_lm_params(k, cfg),
                          jax.random.PRNGKey(0))
    fn = jax.jit(lambda k: program_params(weights.llama_weights(k, m), m))
    got = jax.eval_shape(fn, harness.seed_key(seed))
    if jax.tree.map(lambda a: (a.shape, a.dtype), got) != \
            jax.tree.map(lambda a: (a.shape, a.dtype), want):
        raise harness.RunError("weight tree does not match the program's")
    return jax.block_until_ready(fn(harness.seed_key(seed)))


def make_keys(conf: dict):
    """C3-SL's fixed keys, from the link's own key seed (the codec's keys
    are part of the deployment, as ``C3SLCodec.key_seed`` is): the same
    for every run, so the engine's programs, which hold them as
    constants, are found in the compile cache."""
    import jax
    link = conf["link"]
    return jax.block_until_ready(jax.jit(
        lambda k: weights.hrr_keys(k, link["R"], conf["model"]["hidden_size"])
    )(harness.seed_key(link["key_seed"])))


def make_engine(conf: dict, params, keys, seed: int):
    from repro.serving.engine import BatchedEngine
    e = conf["engine"]
    with warnings.catch_warnings():
        # the kernel read names the reads it leaves on gather (prefill)
        warnings.simplefilter("ignore")
        return BatchedEngine(
            params, model_config(conf), num_slots=e["num_slots"],
            max_len=e["max_len"], codec=conf["link"]["spec"],
            codec_params={"keys": keys}, greedy=True, seed=seed & 0x7FFFFFFF,
            prefill_mode="chunked", chunk_size=e["chunk_size"],
            sync_every=e["sync_every"], kv_layout="paged",
            page_size=e["page_size"], num_pages=e["num_pages"],
            kv_read=e["kv_read"])


def warm_up(eng, conf: dict) -> None:
    """One direct request that drives every program the window uses: a
    ragged two-chunk prefill, decode windows, a retire and a reset."""
    from repro.serving.engine import Request
    e = conf["engine"]
    eng.submit(Request(uid=-1, prompt=[1] * (e["chunk_size"] + 1),
                       max_new_tokens=e["sync_every"] + 2))
    eng.run()
    eng.finished.clear()


# ---------------------------------------------------------------------------
# the engine's C3-SL grouping, read from its dispatches
# ---------------------------------------------------------------------------

class ScheduleRecorder:
    """Logs which request sat in which slot at which position in every
    codec call of the engine, the grouping the reference must reproduce,
    and writes host spans around the engine's steps into the trace.

    It adds no host sync to the engine's loop.  A prefill chunk is read
    from the arguments the engine packed into the prefill program
    (``tokens`` and ``valid``, kept as they were passed and read back once
    the run is over, by :meth:`resolve`).  A decode window is read from
    what the window program returns, at the sync the engine makes there
    anyway: a slot live at the end was live in every step, and one that
    finished in the window decoded from the position after its last
    logged one up to its returned ``pos``.

    :meth:`resolve` returns the log: entries ``(t, kind, rows)``, ``kind``
    "P" for a prefill chunk with rows ``(slot, uid, start, n)``, "D" for
    one decode step with rows ``(slot, uid, pos)`` of the slots live in
    it.  ``faults`` collects any dispatch the log cannot account for.
    """

    def __init__(self, eng):
        import jax
        from jax.profiler import TraceAnnotation
        self._raw: list = []
        self._next: dict[int, int] = {}     # uid -> next decode position
        self.prompts: dict[int, tuple] = {}
        self.faults: list[str] = []
        prefill, window, submit = (eng._prefill_one_chunk, eng._decode_window,
                                   eng.submit)
        boundary = eng._boundary

        def uids():
            return [None if s.req is None else s.req.uid for s in eng.slots]

        def _submit(req):
            submit(req)
            self.prompts[req.uid] = tuple(req.prompt)

        def _prefill():
            with TraceAnnotation("engine.prefill_chunk"):
                prefill()

        def _window(n):
            with TraceAnnotation("engine.decode_window"):
                return window(n)

        def _boundary():
            with TraceAnnotation("engine.boundary"):
                boundary()

        def wrap_prefill_program(prog):
            def run(params, cache, state, tokens, valid, *args):
                self._raw.append((time.monotonic(), "P", (uids(), tokens, valid)))
                return prog(params, cache, state, tokens, valid, *args)
            return run

        def wrap_window_program(prog):
            def run(params, cache, state, *args):
                owner = uids()
                t = time.monotonic()
                out = prog(params, cache, state, *args)
                steps, st = jax.device_get(
                    (out[0], {k: out[2][k] for k in ("pos", "active", "done")}))
                self._window(t, int(steps), owner, st)
                return out
            return run

        for progs in eng._programs.values():
            progs["prefill"] = wrap_prefill_program(progs["prefill"])
            progs["window"] = wrap_window_program(progs["window"])
        eng.submit, eng._prefill_one_chunk = _submit, _prefill
        eng._decode_window, eng._boundary = _window, _boundary

    def _window(self, t, steps: int, owner: list, st: dict) -> None:
        per_step = [[] for _ in range(steps)]
        for j in np.flatnonzero(st["active"]):
            u = owner[j]
            if u is None:
                self.faults.append(f"a decode window ran slot {j}, which "
                                   "holds no request")
                continue
            start = self._next.get(u, len(self.prompts[u]))
            gain = int(st["pos"][j]) - start
            if not 0 <= gain <= steps or (not st["done"][j] and gain != steps):
                self.faults.append(f"a decode window of {steps} steps moved "
                                   f"request {u} by {gain} positions")
                continue
            for step in range(gain):
                per_step[step].append((int(j), u, start + step))
            self._next[u] = start + gain
        self._raw.extend((t, "D", rows) for rows in per_step)

    def resolve(self) -> list:
        """The log, with each prefill chunk's rows read from its arguments:
        the rows its ``valid`` marks, each checked to carry the next
        tokens of its request's prompt."""
        log, fed = [], {}
        for t, kind, payload in self._raw:
            if kind == "D":
                log.append((t, kind, payload))
                continue
            owner, tokens, valid = payload
            tokens, valid = np.asarray(tokens), np.asarray(valid)
            rows = []
            for j in np.flatnonzero(valid.any(-1)):
                u, n = owner[j], int(valid[j].sum())
                start = fed.get(u, 0)
                if u is None or not valid[j, :n].all() or tuple(
                        tokens[j, :n].tolist()) != self.prompts[u][start:start + n]:
                    self.faults.append(f"a prefill chunk fed slot {j} rows "
                                       "that are not its prompt's next tokens")
                    continue
                rows.append((int(j), u, start, n))
                fed[u] = start + n
            log.append((t, "P", rows))
        return log


def log_in(log, t_start: float, t_end: float):
    """(kind, rows) of the log's dispatches made inside [t_start, t_end)."""
    return [(k, rows) for t, k, rows in log if t_start <= t < t_end]


def codec_events(log, uid: int, R: int):
    """For every position of request ``uid``: the (key index, uid, pos) of
    each row of its C3-SL group in the codec call that carried it, and the
    request's own key index.  Returns ({pos: [(k, uid, pos), ...]}, own)."""
    events, own = {}, None
    for _, kind, rows in log:
        if kind == "P":
            mine = [r for r in rows if r[1] == uid]
            if not mine:
                continue
            slot, _, start, n = mine[0]
            own = slot % R
            mates = [r for r in rows if r[0] // R == slot // R]
            for j in range(n):
                events[start + j] = [(r[0] % R, r[1], r[2] + j)
                                     for r in mates if j < r[3]]
        else:
            mine = [r for r in rows if r[1] == uid]
            if not mine:
                continue
            slot, _, pos = mine[0]
            own = slot % R
            events[pos] = [(r[0] % R, r[1], r[2]) for r in rows
                           if r[0] // R == slot // R]
    return events, own


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    t0: float
    t1: float
    records: list
    lag_ms: list
    occupancy: float
    trace_window: tuple | None
    stats0: dict
    stats1: dict
    compiles_in_window: int


async def serve_traffic(eng, conf: dict, mix_name: str, traffic_dir, seed: int,
                        seconds: float, trace_dir, counter) -> Served:
    from jax.profiler import TraceAnnotation
    import jax
    from repro.frontdoor.admission import AdmissionController, TenantPolicy
    from repro.frontdoor.server import FrontDoorServer
    mix = gen.load(mix_name, traffic_dir)
    server = FrontDoorServer(eng, admission=AdmissionController(
        max_queue_depth=4 * mix.get("clients", 16),
        default_policy=TenantPolicy(max_inflight=mix.get("clients", 16))))
    deliver = server._deliver

    async def _deliver():
        with TraceAnnotation("frontdoor.deliver"):
            return await deliver()

    server._deliver = _deliver
    host, port = await server.start()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(harness.BENCH_DIR / "loadgen.py"),
        "--host", host, "--port", str(port), "--traffic", mix_name,
        "--traffic-dir", str(traffic_dir), "--seed", str(seed),
        "--seconds", str(seconds), "--vocab", str(conf["model"]["vocab_size"]),
        "--codec", conf["link"]["spec"], "--warmup-s",
        str(mix.get("warmup_s", 0.0)),
        stdout=asyncio.subprocess.PIPE, env=env, limit=1 << 30)
    state = {"t0": None, "t1": None, "trace": None, "occ": [],
             "stats0": None, "stats1": None, "c0": 0, "c1": 0}
    stop = asyncio.Event()

    async def sample_occupancy():
        while not stop.is_set():
            state["occ"].append((time.monotonic(),
                                 sum(s.req is not None for s in eng.slots)))
            await asyncio.sleep(OCCUPANCY_PERIOD_S)

    async def stop_trace_later(t_end):
        await asyncio.sleep(max(0.0, t_end - time.monotonic()))
        t = time.monotonic()
        jax.profiler.stop_trace()
        state["trace"] = (state["trace"][0], t)

    sampler = asyncio.create_task(sample_occupancy())
    tracer = None
    done = None
    try:
        while True:
            line = await proc.stdout.readline()
            if not line:
                break
            msg = json.loads(line)
            if msg["ev"] == "window_start":
                state["t0"] = msg["t"]
                state["stats0"] = dict(eng.stats)
                state["c0"] = counter.count()
                if trace_dir is not None:
                    # host spans, not a trace of every Python call
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(str(trace_dir),
                                             profiler_options=opts)
                    state["trace"] = (time.monotonic(), None)
                    tracer = asyncio.create_task(stop_trace_later(
                        state["trace"][0] + min(TRACE_SECONDS, seconds)))
            elif msg["ev"] == "window_end":
                state["t1"] = msg["t"]
                state["stats1"] = dict(eng.stats)
                state["c1"] = counter.count()
            elif msg["ev"] == "done":
                done = msg
        rc = await proc.wait()
        if rc != 0 or done is None:
            raise harness.RunError(f"load generator exited with {rc}")
        if tracer is not None:
            await tracer
    finally:
        stop.set()
        await sampler
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
        await server.stop(drain=False)
    if server.tick_error is not None:
        raise harness.RunError(f"front door tick loop failed: "
                               f"{server.tick_error!r}")
    t0, t1 = state["t0"], state["t1"]
    occ = state["occ"]
    num = den = 0.0
    for (ta, na), (tb, _) in zip(occ, occ[1:]):
        a, b = max(ta, t0), min(tb, t1)
        if b > a:
            num += na * (b - a)
            den += b - a
    return Served(t0=t0, t1=t1, records=done["requests"], lag_ms=done["lag_ms"],
                  occupancy=num / den / eng.num_slots if den else 0.0,
                  trace_window=state["trace"], stats0=state["stats0"],
                  stats1=state["stats1"],
                  compiles_in_window=state["c1"] - state["c0"])


def end_to_end(s: Served) -> tuple[dict, int, int, str]:
    """(metrics, attempted, failed, summary) from the clients' records:
    tokens delivered in the window over its length; the 90th percentile of
    time to first token over requests finished in it; the 95th percentile
    of the gaps between token arrivals in it (tokens of one frame: 0)."""
    t0, t1 = s.t0, s.t1
    tokens = 0
    ttft, gaps = [], []
    attempted = failed = 0
    for r in s.records:
        if t0 <= r["t_send"] < t1:
            attempted += 1
            if r["err"] is not None:
                failed += 1
        if r["err"] is not None:
            continue
        prev = None
        for t, n in r["bursts"]:
            if t0 <= t < t1:
                tokens += n
                if prev is not None:
                    gaps.append(t - prev)
                gaps.extend([0.0] * (n - 1))
            prev = t
        if t0 <= r["t_done"] < t1 and r["bursts"]:
            ttft.append(r["bursts"][0][0] - r["t_send"])
    metrics = {
        "gen_tokens_per_s": {"value": tokens / (t1 - t0), "unit": "tokens/s"},
        "ttft_p90_ms": {"value": 1e3 * harness.percentile(ttft, 90), "unit": "ms"},
        "itl_p95_ms": {"value": 1e3 * harness.percentile(gaps, 95), "unit": "ms"},
    }
    summary = (f"window {t1 - t0:.3f}s: {tokens} tokens delivered, "
               f"{len(ttft)} requests finished, {len(gaps)} token gaps, "
               f"{attempted} sent, {failed} failed; ttft p50 "
               f"{1e3 * harness.percentile(ttft, 50):.1f} ms, p90 "
               f"{metrics['ttft_p90_ms']['value']:.1f} ms; itl p95 "
               f"{metrics['itl_p95_ms']['value']:.1f} ms")
    return metrics, attempted, failed, summary


# ---------------------------------------------------------------------------
# the check against the plain reference
# ---------------------------------------------------------------------------

def sample_requests(s: Served, seed: int) -> list[dict]:
    """The requests finished in the window: all of them up to
    SAMPLE_REQUESTS, else the longest and others drawn from the seed."""
    fin = [r for r in s.records
           if r["err"] is None and s.t0 <= r["t_done"] < s.t1]
    if not fin:
        return []
    fin.sort(key=lambda r: (r["c"], r["i"]))
    longest = max(fin, key=lambda r: len(r["tokens"]))
    rest = [r for r in fin if r is not longest]
    order = gen.rng(seed, 7).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:SAMPLE_REQUESTS - 1]]


def check(conf: dict, seed: int, s: Served, recorder_log, prompts_by_uid,
          *, control: bool = False) -> dict:
    """Recompute the sampled requests with the plain reference and read,
    for each served token, how far its logit lies below the reference's
    best.  With ``control`` the reference one step of precision down
    (the configuration's ``check.control``: the dtype and precision of
    every product, and ``"weights": "int8"`` for every matrix quantized
    per output channel) stands in for the program: its first choice at
    each position is read the same way."""
    import jax
    import jax.numpy as jnp
    from chipbench.references import llama_split as ref_lib
    m = conf["model"]
    R = conf["link"]["R"]
    T = conf["engine"]["max_len"]
    n_rows = conf["check"]["rows"]
    uid_of = {p: u for u, p in prompts_by_uid.items()}
    by_uid = {}
    for r in s.records:
        p = tuple(gen.prompt_tokens(seed, r["c"], r["i"], r["L"],
                                    m["vocab_size"]))
        if r["err"] is None and p in uid_of:
            by_uid[uid_of[p]] = r
    sample = sample_requests(s, seed)
    out = {"faults": [], "gaps": [], "ctl_gaps": [], "tokens": 0, "same": 0,
           "requests": len(sample)}
    key = harness.seed_key(seed)
    W = jax.jit(lambda k: weights.llama_weights(k, m))(key)
    keys = jax.jit(lambda k: weights.hrr_keys(k, R, m["hidden_size"]))(
        harness.seed_key(conf["link"]["key_seed"]))
    circ = jax.jit(ref_lib.circulants)(keys)
    ref = ref_lib.LlamaSplit(m, conf["cut"])
    if control:
        c = conf["check"]["control"]
        Wc = ref_lib.int8_weights(W) if c.get("weights") == "int8" else W
        circ_c = circ.astype(c["dtype"])
        ctl = ref_lib.LlamaSplit(m, conf["cut"], c["dtype"], c["precision"])

    def seq_of(u):
        r = by_uid[u]
        return list(prompts_by_uid[u]) + r["tokens"][:-1]

    def padded(tokens):
        a = np.zeros((T,), np.int32)
        a[:len(tokens)] = tokens
        return jnp.asarray(a)

    feats, feats_c = {}, {}
    for r in sample:
        u = next((u for u, rr in by_uid.items() if rr is r), None)
        if u is None:
            out["faults"].append(f"request {r['c']}/{r['i']} not in the "
                                 "engine's log")
            continue
        events, own = codec_events(recorder_log, u, R)
        L = len(prompts_by_uid[u])
        M = len(r["tokens"])
        need = L + M - 1
        if sorted(events) != list(range(need)):
            out["faults"].append(f"request {u}: the log carries positions "
                                 f"{len(events)} of {need}")
            continue
        mates = {mu for ev in events.values() for _, mu, _ in ev}
        if any(mu not in by_uid for mu in mates):
            out["faults"].append(f"request {u}: a group mate has no record")
            continue
        for mu in mates:
            if mu not in feats:
                feats[mu] = ref.bottom(W, padded(seq_of(mu)))
                if control:
                    feats_c[mu] = ctl.bottom(Wc, padded(seq_of(mu)))
        idx = sorted(mates)
        slot_of = {mu: i for i, mu in enumerate(idx)}
        gi = np.zeros((T, R), np.int32)
        gp = np.zeros((T, R), np.int32)
        gm = np.zeros((T, R), np.float32)
        for pos, ev in events.items():
            for k, mu, mp in ev:
                gi[pos, k], gp[pos, k], gm[pos, k] = slot_of[mu], mp, 1.0
        rows = np.zeros((n_rows,), np.int32)
        rows[:M] = np.arange(L - 1, L - 1 + M)

        def decoded(fdict, codec_fn, c):
            Z = jnp.stack([fdict[mu] for mu in idx])
            Zg = Z[jnp.asarray(gi), jnp.asarray(gp)] * jnp.asarray(gm)[..., None]
            return codec_fn(c, Zg.astype(Z.dtype), jnp.int32(own))

        x = decoded(feats, ref.codec, circ)
        logits = np.asarray(ref.top(W, x, jnp.asarray(rows)))[:M]
        served = np.asarray(r["tokens"])
        best = logits.max(-1)
        gap = best - logits[np.arange(M), served]
        out["gaps"].extend(gap.tolist())
        out["tokens"] += M
        out["same"] += int((logits.argmax(-1) == served).sum())
        if control:
            xc = decoded(feats_c, ctl.codec, circ_c)
            lc = np.asarray(ctl.top(Wc, xc, jnp.asarray(rows)))[:M]
            pick = lc.argmax(-1)
            out["ctl_gaps"].extend((best - logits[np.arange(M), pick]).tolist())
    return out


def numbers(gaps) -> dict:
    """Statistics of the served tokens' gaps below the reference's best
    logit: the widest, the mean, the mean square and the share of tokens
    that are not the reference's first choice."""
    if not gaps:
        return {}
    g = np.asarray(gaps, np.float64)
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
            "mean_sq_gap": float((g * g).mean()),
            "disagree_share": float((g > 0).mean())}


def judge(conf: dict, nums: dict, faults: list, tokens: int) -> list[dict]:
    """Each number compared, beside its limit."""
    check = conf["check"]
    out = [{"name": k, "value": nums.get(k), "limit": v,
            "ok": nums.get(k) is not None and nums[k] <= v}
           for k, v in check["limits"].items()]
    out.append({"name": "faults", "value": len(faults), "limit": 0,
                "ok": not faults})
    out.append({"name": "served_tokens_checked", "value": tokens,
                "limit": check["min_tokens"], "ok": tokens >= check["min_tokens"]})
    return out


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

def run(ctx: dict) -> dict:
    """One run of a serve cell.  ``ctx``: conf, traffic, traffic_dir,
    seed, seconds, trace_dir (None for --trace 0), devices, counter,
    clock, control."""
    import jax
    conf, seed = ctx["conf"], ctx["seed"]
    clock, counter = ctx["clock"], ctx["counter"]
    c_start = counter.seconds
    params = make_params(conf, seed)
    keys = make_keys(conf)
    t_init = clock()
    eng = make_engine(conf, params, keys, seed)
    warm_up(eng, conf)
    t_warm = clock()
    recorder = ScheduleRecorder(eng)
    served = asyncio.run(serve_traffic(
        eng, conf, ctx["traffic"], ctx["traffic_dir"], seed, ctx["seconds"],
        ctx["trace_dir"], counter))
    setup_s = served.t0 - clock.t0
    harness.log(
        f"setup {setup_s:.3f}s: init {t_init:.3f}s, warm-up "
        f"{t_warm - t_init:.3f}s, first wave {setup_s - t_warm:.3f}s; "
        f"compile {counter.seconds - c_start:.3f}s, persistent cache "
        f"{counter.cache_hits} hits / {counter.cache_misses} misses")
    harness.log(f"compilations inside the window: {served.compiles_in_window}")
    lag = served.lag_ms
    harness.log("generator lag: " + (
        f"median {harness.percentile(lag, 50):.3f} ms, max {max(lag):.3f} ms"
        if lag else "none"))
    harness.log(f"slot occupancy {served.occupancy:.4f}; engine counters in "
                f"the window: " + ", ".join(
                    f"{k} {served.stats1[k] - served.stats0[k]}"
                    for k in ("dispatches", "decode_steps", "prefill_chunks",
                              "evictions", "wire_bytes_fwd")))
    device = harness.device_info(ctx["devices"])
    metrics, attempted, failed, summary = end_to_end(served)
    harness.log(summary)
    metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    log, prompts = recorder.resolve(), recorder.prompts
    faults = list(recorder.faults)
    if served.stats1["evictions"] != served.stats0["evictions"]:
        faults.append("the engine evicted a request; the log cannot follow")
    per_layer_ctx = None
    if ctx["trace_dir"] is not None:
        per_layer_ctx = {"conf": conf, "served": served, "log": log,
                         "trace_window": served.trace_window,
                         "device": device, "trace_dir": ctx["trace_dir"],
                         "devices": list(range(len(ctx["devices"]))),
                         "spans": SPANS}
    # free the program's state before the reference runs
    for leaf in jax.tree.leaves((eng.params, eng.cache, eng.state, keys)):
        leaf.delete()
    del eng, recorder, params, keys
    gc.collect()
    jax.clear_caches()
    harness.log("device bytes in use once the engine is freed: " + str(
        (ctx["devices"][0].memory_stats() or {}).get("bytes_in_use")))
    if not ctx.get("check", True):
        return {"metrics": metrics, "served": served, "device": device}
    t_ref = time.monotonic()
    got = check(conf, seed, served, log, prompts, control=ctx.get("control", False))
    faults += got["faults"]
    harness.log(f"reference: {got['tokens']} served tokens of "
                f"{got['requests']} requests in {time.monotonic() - t_ref:.3f}s; "
                f"reference argmax equals the served token at "
                f"{got['same']} of them")
    for f in faults:
        harness.log(f"fault: {f}")
    nums = numbers(got["gaps"])
    checks = judge(conf, nums, faults, got["tokens"])
    result = {"correct": all(c["ok"] for c in checks), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device,
              "checks": checks, "numbers": nums}
    if ctx.get("control", False):
        # the control in the program's place, judged by the same limits
        ctl = numbers(got["ctl_gaps"])
        checks = judge(conf, ctl, faults, got["tokens"])
        result.update(correct=all(c["ok"] for c in checks), checks=checks,
                      numbers=ctl, program_numbers=nums,
                      program_correct=result["correct"])
    if per_layer_ctx is not None:
        result["per_layer_ctx"] = per_layer_ctx
    return result
