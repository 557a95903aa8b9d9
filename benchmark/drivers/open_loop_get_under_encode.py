"""Degraded GETs in an open loop while the master's maintenance script
runs `ec.encode` on the same volume server: `open_loop_get`'s volume,
loss, schedule, window and samples, letter for letter, and behind them a
second, sealed volume that a child (`maint.py`) generates again and again
from before the window until after it.

The two cells differ by the background alone. This driver adds what the
background needs: the child and its passes, how much of the window a
generate was in flight, what the passes encoded, and the comparison of
every pass's files with the plain reference (`reference_warmdown`).
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import threading
import time

import ops
import reference
import reference_warmdown
from maint import file_hash, probe
from run import HERE, child_start, load_module

base = load_module("drivers", "open_loop_get")

WARM_LIMIT_S = 120   # the passes before the window, all of them
STOP_LIMIT_S = 120   # "stop" -> the pass in flight has ended
ENCODED = "seaweedfs_tpu_ec_encode_input_bytes_total"
DISPATCHES = 'seaweedfs_tpu_ec_stage_seconds_count{stage="ec.dispatch"}'


def source_hashes(vol: ops.Volume) -> dict[str, str]:
    """The sealed volume's `.dat` and `.idx` as they stand: their bytes'
    hash and which file it is."""
    out = {}
    for ext in (".dat", ".idx"):
        st = os.stat(vol.base + ext)
        out[ext] = (f"{file_hash(vol.base + ext)} inode {st.st_ino} "
                    f"bytes {st.st_size}")
    return out


class Maint:
    """The script's runner and what it has reported so far."""

    def __init__(self, ctx: ops.Ctx, vol: ops.Volume):
        self.passes: list[dict] = []
        self.done = threading.Event()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "maint.py"), json.dumps(
                {"volume": ctx.volume_url, "vid": vol.vid, "base": vol.base,
                 "exts": reference_warmdown.exts(ctx.k, ctx.m),
                 "probe": DISPATCHES})],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1, preexec_fn=child_start)
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            try:
                msg = json.loads(line)
            except ValueError:
                ops.say("maint: " + line.rstrip())
                continue
            if "done" in msg:
                self.done.set()
            else:
                self.passes.append(msg)

    def alive(self) -> None:
        if self.proc.poll() is not None and not self.done.is_set():
            raise SystemExit(f"the maintenance child ended (rc "
                             f"{self.proc.returncode}) after "
                             f"{len(self.passes)} passes")

    def stop(self) -> None:
        """Finish the pass in flight, then end."""
        self.alive()
        if self.proc.poll() is None:
            self.proc.stdin.write("stop\n")
            self.proc.stdin.flush()
        if not self.done.wait(STOP_LIMIT_S):
            self.proc.kill()
            raise SystemExit("the maintenance child did not stop")
        self.proc.wait(30)


plan = base.plan


def setup(ctx: ops.Ctx) -> dict:
    # before volume 0 is encoded and its source retired: the master
    # lists what `fill_store` wrote
    sealed = ctx.volumes()[1]
    ops.keep_source(sealed)
    state = base.setup(ctx)  # volume 0: encoded, 3 + 1 lost, warmed
    bg = ctx.traffic["background"]
    state.update(
        sealed=sealed, source=source_hashes(sealed), k=ctx.k, m=ctx.m,
        # what one pass hands to the coder: k rows of a shard file's
        # length, the last stripe row's zero padding included
        pass_bytes=ctx.k * reference.shard_size(
            sealed.dat_bytes, ctx.k, ctx.large_block, ctx.small_block))
    dispatched = [probe(ctx.volume_url, DISPATCHES) or 0.0]
    maint = state["maint"] = Maint(ctx, sealed)
    # whole passes before the clock starts, `warm_passes` or more, until
    # two in a row dispatched the same number of batches: the governor
    # may step between passes (a deeper queue halves the batch to stay in
    # its staging budget), and a new width is a new executable
    deadline = time.time() + WARM_LIMIT_S
    batches: list[float] = []
    while len(batches) < bg["warm_passes"] or batches[-1] != batches[-2]:
        maint.alive()
        if time.time() > deadline:
            maint.proc.kill()
            raise SystemExit(f"batches a pass {batches} in {WARM_LIMIT_S}s"
                             ": the encode never settled on a width")
        time.sleep(0.01)
        for p in maint.passes[len(batches):]:
            dispatched.append(p["probe"] or 0.0)
            batches.append(dispatched[-1] - dispatched[-2])
    state["warm"] = {"passes": len(batches), "batches_a_pass": batches}
    return state


def window(ctx: ops.Ctx, state: dict) -> dict:
    state["maint"].alive()
    before = probe(ctx.volume_url, ENCODED)
    t0 = time.monotonic()
    samples = base.window(ctx, state)
    t1 = time.monotonic()
    after = probe(ctx.volume_url, ENCODED)
    state["maint"].alive()
    samples.update(t0=t0, t1=t1, encoded=(
        None if before is None or after is None else after - before))
    return samples


def background(state: dict, samples: dict) -> dict:
    """What the generates did in [t0, t1], from the child's own record
    (and the program's counter of encoded bytes where it has one)."""
    t0, t1 = samples["t0"], samples["t1"]
    passes = list(state["maint"].passes)
    ended = [p for p in passes if t0 <= p["end"] <= t1]
    in_flight = sum(max(0.0, min(t1, p["end"]) - max(t0, p["start"]))
                    for p in passes)
    # a pass still in flight at t1 has not reported: it began when the
    # last one's hashes were done
    last = max((p["end"] + p["gap_s"] for p in passes), default=t0)
    if not any(p["end"] > t1 for p in passes):
        in_flight += max(0.0, t1 - max(t0, last))
    counted = samples["encoded"]
    by_passes = sum(
        state["pass_bytes"]
        * max(0.0, min(t1, p["end"]) - max(t0, p["start"]))
        / (p["end"] - p["start"]) for p in passes)
    encoded = counted if counted is not None else by_passes
    seconds = [p["end"] - p["start"] for p in ended]
    return {"passes_in_window": len(ended),
            "passes_before_window": sum(p["end"] < t0 for p in passes),
            "pass_s_median": statistics.median(seconds) if seconds else None,
            "pass_gap_s_median": statistics.median(
                p["gap_s"] for p in ended) if ended else None,
            "generates_shed": sum(p["shed"] for p in ended),
            # more than one value: the encode changed its width, and
            # compiled, inside the window
            "batches_a_pass": sorted({
                b["probe"] - a["probe"] for a, b in zip(passes, passes[1:])
                if t0 <= b["end"] <= t1 and None not in (a["probe"],
                                                         b["probe"])}),
            "gap_share": 1.0 - in_flight / (t1 - t0),
            "encode_input_bytes": encoded,
            "encode_input_bytes_from": (
                "program_counter" if counted is not None else "passes"),
            "encode_input_bytes_by_passes": by_passes,
            "encode_columns": encoded / state["k"],
            "encode_rows_out": state["m"],
            "warm": state["warm"]}


def summary(state: dict, samples: dict) -> dict:
    out = base.summary(state, samples)
    out["facts"].update(background(state, samples))
    return out


def verify(ctx: ops.Ctx, state: dict, samples: dict) -> dict:
    maint, sealed = state["maint"], state["sealed"]
    maint.stop()
    passes = list(maint.passes)
    # the plain reference over the kept source, and the files as the
    # last pass left them, byte for byte
    ref = reference_warmdown.walk(
        sealed.ref, ctx.k, ctx.m, ctx.large_block, ctx.small_block,
        against=sealed.base)
    # the control: a pass that encoded with another matrix
    control_differing = reference_warmdown.files_differing(
        ref["hashes"], ref["control_hashes"])
    pass_files = sum(reference_warmdown.files_differing(
        ref["hashes"], ref["control_hashes"] if ctx.control
        else p["hashes"]) for p in passes)
    final_files = control_differing if ctx.control \
        else len(ref["differing"])
    source_changed = sum(state["source"][ext] != now for ext, now
                         in source_hashes(sealed).items())
    ops.drop_source(sealed)
    out = base.verify(ctx, state, samples)  # the GET side's four checks
    facts = background(state, samples)
    bg = ctx.traffic["background"]
    # `min_passes` is of the benchmark's window; a shorter one (a
    # rehearsal, a sweep's) is held to its share
    need = int(bg["min_passes"] * min(1.0, ctx.seconds / bg["window_s"]))
    facts.update(passes_verified=len(passes), min_passes=need,
                 control_pass_files_differing=control_differing,
                 final_files_differing=ref["differing"])
    out["facts"].update(facts)
    out["checks"] += [
        ops.check("pass_files_differing", pass_files, 0),
        ops.check("final_files_differing", final_files, 0),
        ops.check("passes_short", max(0, need - facts["passes_in_window"]),
                  0),
        ops.check("gap_share", round(facts["gap_share"], 4),
                  bg["max_gap_share"]),
        ops.check("source_files_changed", source_changed, 0)]
    return out
