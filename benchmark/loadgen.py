#!/usr/bin/env python3
"""The load generator: a child of run.py, pinned to JAX_PLATFORMS=cpu,
so that client work does not share the volume server's interpreter lock
and never touches the chip.

    python3 benchmark/loadgen.py '<context as JSON>'

It loads the driver the traffic file names (`drivers/<name>.py`) and
walks it through a run: `setup` (fills, encodes what the window needs
encoded, warms the window's shapes), then on "go" the timed `window`,
then on "verify" the comparison with the plain reference. A driver
module has:

    room(config, traffic) -> (largest file in bytes, disk bytes needed)
    setup(ctx) -> state
    window(ctx, state) -> samples     # the only timed part
    verify(ctx, state, samples) -> {"metrics", "attempted", "failed",
                                    "checks", "facts"}

and, for `run.py --sweep` alone, plan(ctx, state, rate) and
summary(state, samples).
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main() -> None:
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("the load generator must be pinned to "
                         "JAX_PLATFORMS=cpu")
    import ops
    from run import load_module
    ctx = ops.Ctx(json.loads(sys.argv[1]))
    driver = load_module("drivers", ctx.traffic["driver"])
    t0 = time.time()
    state = driver.setup(ctx)
    ops.emit("ready", setup={"loadgen_s": time.time() - t0})
    if sys.stdin.readline().strip() != "go":
        raise SystemExit("expected go")
    for rate in ctx.raw.get("sweep", []):
        # the sweep that found the mix's rate: a window at each, after
        # one set-up, reported and never verified
        driver.plan(ctx, state, rate)
        got = driver.summary(state, driver.window(ctx, state))
        ops.say(f"sweep {rate}/s: " + json.dumps(
            {"get_p50_ms": got["metrics"]["get_p50_ms"]["value"],
             **got["facts"]}))
    if ctx.raw.get("sweep"):
        driver.plan(ctx, state, ctx.traffic["load"]["rate_per_s"])
    t0 = time.perf_counter()
    ops.emit("window_start")
    samples = driver.window(ctx, state)
    ops.emit("window_end", window_s=time.perf_counter() - t0)
    if sys.stdin.readline().strip() != "verify":
        raise SystemExit("expected verify")
    t0 = time.time()
    out = driver.verify(ctx, state, samples)
    out["correct"] = bool(out["failed"] == 0
                          and ops.all_within(out["checks"]))
    ops.say(f"verified in {time.time() - t0:.1f}s")
    ops.emit("result", **out)


if __name__ == "__main__":
    main()
