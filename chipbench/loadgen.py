"""Load generator: the clients of a run, in a process of their own.

    python chipbench/loadgen.py --port P --traffic NAME --seed N \\
        --seconds S --vocab V --codec SPEC

The benchmark starts it with ``JAX_PLATFORMS=cpu`` so it can never take
the chip.  It opens one front-door connection per client of the plan,
drives them (closed loop: each client's next request when its last one
has completed; open loop: each request at its due second), and writes
JSON lines to standard output:

* ``{"ev": "window_start", "t": ...}`` once the warm-up is over (closed
  loop: every client's first request has completed);
* ``{"ev": "window_end", "t": ...}`` ``--seconds`` later, after which no
  request is sent and those in flight are waited for;
* ``{"ev": "done", "requests": [...], "lag_ms": [...]}`` with, for every
  request sent: client, index, send time, each burst of tokens with its
  arrival time, the tokens, and any error.

Times are ``time.monotonic()``, the clock the benchmark's process reads
too.  ``lag_ms`` is how late the generator ran: in a closed loop, the time
from a result's arrival to the client's next send; in an open loop, from
a request's due time to its send.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench.traffic import generator as gen  # noqa: E402


def emit(obj) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


class Record:
    def __init__(self, client: int, index: int, prompt_len: int):
        self.client, self.index, self.prompt_len = client, index, prompt_len
        self.t_send = self.t_done = None
        self.bursts: list[tuple[float, int]] = []
        self.tokens: list[int] = []
        self.err = None

    def on_tokens(self, tokens):
        self.bursts.append((time.monotonic(), len(tokens)))

    def as_dict(self) -> dict:
        return {"c": self.client, "i": self.index, "L": self.prompt_len,
                "t_send": self.t_send,
                "t_done": self.t_done, "bursts": self.bursts,
                "tokens": self.tokens, "err": self.err}


async def one_request(client, rec: Record, prompt, max_new: int):
    from repro.frontdoor.client import FrontDoorError
    rec.t_send = time.monotonic()
    try:
        out = await client.generate(prompt, max_new=max_new, retries=1000)
    except FrontDoorError as e:
        rec.err = f"{type(e).__name__}: {e}"
        rec.t_done = time.monotonic()
        return
    rec.t_done = time.monotonic()
    rec.tokens = [int(t) for t in out["tokens"]]
    streamed = sum(n for _, n in rec.bursts)
    if streamed < len(rec.tokens):       # tokens that came only in RESULT
        rec.bursts.append((rec.t_done, len(rec.tokens) - streamed))


async def run(args) -> None:
    from repro.frontdoor.client import FrontDoorClient
    mix = gen.load(args.traffic, Path(args.traffic_dir))
    plan = gen.plan(mix, args.seed, vocab=args.vocab,
                    horizon_s=args.warmup_s + args.seconds,
                    traffic_dir=Path(args.traffic_dir))
    records: list[Record] = []
    live: dict[int, Record] = {}
    lag: list[float] = []
    conns = []
    for c, spec in enumerate(plan["clients"]):
        def on_tokens(rid, tokens, c=c):
            rec = live.get(c)
            if rec is not None:
                rec.on_tokens(tokens)
        conns.append(await FrontDoorClient.open(
            args.host, args.port, tenant=spec["tenant"], codec=args.codec,
            on_tokens=on_tokens))
    emit({"ev": "ready", "t": time.monotonic()})

    def prompt(c, i, r):
        return gen.prompt_tokens(args.seed, c, i, r["prompt_len"], args.vocab)

    window = {"end": None}

    if plan["loop"] == "closed":
        first_wave = asyncio.Event()
        pending_first = {"n": len(conns)}

        async def client_loop(c):
            reqs = plan["clients"][c]["requests"]
            t_prev = None
            for i, r in enumerate(reqs):
                if window["end"] is not None and time.monotonic() >= window["end"]:
                    return
                rec = Record(c, i, r["prompt_len"])
                records.append(rec)
                live[c] = rec
                p = prompt(c, i, r)
                if t_prev is not None:
                    lag.append((time.monotonic() - t_prev) * 1e3)
                await one_request(conns[c], rec, p, r["max_new"])
                t_prev = time.monotonic()
                if i == 0:
                    pending_first["n"] -= 1
                    if pending_first["n"] == 0:
                        first_wave.set()
            raise RuntimeError(f"client {c} ran out of planned requests")

        tasks = [asyncio.create_task(client_loop(c)) for c in range(len(conns))]
        await first_wave.wait()
        t0 = time.monotonic()
    else:
        t_start = time.monotonic()
        t0 = t_start + plan["warmup_s"]
        sends = sorted((r["due"], c, i) for c, spec in enumerate(plan["clients"])
                       for i, r in enumerate(spec["requests"]))

        async def send(c, i, due):
            r = plan["clients"][c]["requests"][i]
            rec = Record(c, i, r["prompt_len"])
            records.append(rec)
            p = prompt(c, i, r)
            lag.append((time.monotonic() - (t_start + due)) * 1e3)
            await one_request_open(conns[c], rec, p, r["max_new"])

        async def one_request_open(client, rec, p, max_new):
            # an open-loop client multiplexes its requests on one
            # connection: route token bursts by rid
            from repro.frontdoor.client import BusyError, FrontDoorError
            rec.t_send = time.monotonic()
            try:
                rid = await client.submit(p, max_new=max_new)
                routes[(id(client), rid)] = rec
                out = await client.result(rid)
            except (BusyError, FrontDoorError) as e:
                rec.err = f"{type(e).__name__}: {e}"
                rec.t_done = time.monotonic()
                return
            rec.t_done = time.monotonic()
            rec.tokens = [int(t) for t in out["tokens"]]
            streamed = sum(n for _, n in rec.bursts)
            if streamed < len(rec.tokens):
                rec.bursts.append((rec.t_done, len(rec.tokens) - streamed))

        routes: dict = {}
        for c, conn in enumerate(conns):
            def on_tokens(rid, tokens, conn=conn):
                rec = routes.get((id(conn), rid))
                if rec is not None:
                    rec.on_tokens(tokens)
            conn.on_tokens = on_tokens

        async def scheduler():
            for due, c, i in sends:
                if t_start + due >= t0 + args.seconds:
                    return
                delay = t_start + due - time.monotonic()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.create_task(send(c, i, due)))

        tasks: list = []
        sched = asyncio.create_task(scheduler())
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
    emit({"ev": "window_start", "t": t0})
    window["end"] = t0 + args.seconds
    await asyncio.sleep(max(0.0, window["end"] - time.monotonic()))
    emit({"ev": "window_end", "t": window["end"]})
    if plan["loop"] != "closed":
        await sched
    results = await asyncio.gather(*tasks, return_exceptions=True)
    for r in results:
        if isinstance(r, BaseException):
            raise r
    for conn in conns:
        await conn.close()
    emit({"ev": "done", "requests": [r.as_dict() for r in records],
          "lag_ms": lag})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--traffic-dir", default=str(gen.TRAFFIC_DIR))
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--warmup-s", type=float, default=0.0)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--codec", required=True)
    asyncio.run(run(ap.parse_args()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
