"""Degraded GETs to the chip's server of four while the dead peer's shards
are rebuilt there: `open_loop_get_cluster`'s peers, volume, layout, loss,
schedule, window and checks, letter for letter, and behind them a second
EC volume, spread and moved to its own seed layout on the same four
servers, that a child (`repair.py`) repairs again and again through the
shell's `ec.rebuild`, from before the window until after it.

The two cells differ by the background alone. This driver adds what the
background needs: the second volume's encode, spread and moves (made
where the cluster driver moves the first one, before the same peer
dies), the child and its passes, how much of the window a repair was in
flight, and the comparison of every pass's rebuilt files with the plain
reference (`reference_rebuild`).
"""

from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import threading
import time

import datagen
import ops
import reference
import reference_cluster
import reference_rebuild
import reference_warmdown
from maint import probe
from run import HERE, child_start, load_module

base = load_module("drivers", "open_loop_get_cluster")
encode = load_module("drivers", "open_loop_get_under_encode")

WARM_LIMIT_S = 120   # the passes before the window, all of them
REBUILT = "seaweedfs_tpu_ec_rebuild_input_bytes_total"
DISPATCHES = 'seaweedfs_tpu_ec_stage_seconds_count{stage="ec.dispatch"}'
STAGE = "seaweedfs_tpu_ec_stage_seconds"


class Repair(encode.Maint):
    """The script's runner, `repair.py`, and what it has reported so far:
    the warm-down cell's runner (its reader, `alive` and `stop`) on
    another script."""

    def __init__(self, ctx: ops.Ctx, vol: ops.Volume):
        self.passes: list[dict] = []
        self.done = threading.Event()
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "repair.py"), json.dumps(
                {"master": ctx.raw["master"], "volume": ctx.volume_url,
                 "vid": vol.vid, "collection": ctx.collection,
                 "base": vol.base, "total_shards": ctx.k + ctx.m,
                 "probe": DISPATCHES})],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1, preexec_fn=child_start)
        threading.Thread(target=self._read, daemon=True).start()


def seed_layout(ctx: ops.Ctx, vol: ops.Volume) -> tuple[list[int], dict]:
    """The seed's loss and layout of one volume (the cluster driver's)."""
    load = ctx.traffic["load"]
    lost = datagen.lost_shards(ctx.seed, vol.index, ctx.k, ctx.m,
                               load["lost_data"], load["lost_parity"])
    return lost, reference_cluster.seed_layout(
        datagen.shard_permutation(ctx.seed, vol.index, ctx.k).tolist(),
        lost, ctx.k, ctx.m)


def setup(ctx: ops.Ctx) -> dict:
    vol, queued = ctx.volumes()[:2]
    ops.keep_source(queued)
    lost, layout = seed_layout(ctx, queued)
    _, first = seed_layout(ctx, vol)
    move_first = base.move_to
    made: dict = {}

    def move_to(ctx: ops.Ctx, vid: int, want: dict[str, list[int]]) -> int:
        """The cluster driver moves volume 0 to its layout just before
        the doomed peer dies: the queued volume is encoded on the chip,
        spread by the program's own plan and moved to its own layout
        then too, on the same servers in the same roles."""
        moved = move_first(ctx, vid, want)
        role = {r: url for url, sids in want.items()
                for r in reference_cluster.ROLES if first[r] == sids}
        made["want"] = {role[r]: layout[r] for r in reference_cluster.ROLES}
        t0 = time.time()
        ctx.ec.encode_many([queued.vid], ctx.collection)
        base.held_when(ctx, queued.vid, lambda h: sum(map(len, h.values()))
                       == ctx.k + ctx.m)
        moved += move_first(ctx, queued.vid, made["want"])
        made["held"] = base.held_when(ctx, queued.vid,
                                      lambda h: h == made["want"])
        made["s"] = time.time() - t0
        return moved

    base.move_to = move_to
    try:
        state = base.setup(ctx)  # volume 0: spread, moved, a peer killed
    finally:
        base.move_to = move_first
    doomed = state["peers"][0].url
    alive = {u: s for u, s in made["want"].items() if u != doomed}
    after_kill = base.held_when(ctx, queued.vid, lambda h: h == alive)
    with open(queued.ref + ".idx", "rb") as f:
        ecx = reference.sorted_ecx(f.read())
    state["shards_misplaced"] += (
        reference_cluster.misplaced(made["held"], made["want"])
        + reference_cluster.misplaced(after_kill, alive))
    state["cluster"]["queued_encode_moves_s"] = made["s"]
    state.update(queued=queued, queued_lost=lost, queued_chip=layout["chip"],
                 queued_ecx=ecx, k=ctx.k, pass_bytes=ctx.k * reference
                 .shard_size(queued.dat_bytes, ctx.k, ctx.large_block,
                             ctx.small_block))
    repair = state["repair"] = Repair(ctx, queued)
    # whole passes before the clock starts, `warm_passes` or more, until
    # two in a row dispatched the same number of batches: the governor
    # may step between passes, and a new width is a new executable
    bg = ctx.traffic["background"]
    dispatched = [probe(ctx.volume_url, DISPATCHES) or 0.0]
    deadline = time.time() + WARM_LIMIT_S
    batches: list[float] = []
    while len(batches) < bg["warm_passes"] or batches[-1] != batches[-2]:
        repair.alive()
        if time.time() > deadline:
            repair.proc.kill()
            raise SystemExit(f"batches a pass {batches} in {WARM_LIMIT_S}s"
                             ": the rebuild never settled on a width")
        time.sleep(0.01)
        for p in repair.passes[len(batches):]:
            dispatched.append(p["probe"] or 0.0)
            batches.append(dispatched[-1] - dispatched[-2])
    state["warm"] = {"passes": len(batches), "batches_a_pass": batches}
    return state


plan = base.plan


def window(ctx: ops.Ctx, state: dict) -> dict:
    state["repair"].alive()
    before = base.counters(ctx.volume_url)
    t0 = time.monotonic()
    samples = base.window(ctx, state)
    t1 = time.monotonic()
    after = base.counters(ctx.volume_url)
    state["repair"].alive()
    samples.update(t0=t0, t1=t1, counters={
        k: v - before[k] for k, v in after.items() if k in before})
    return samples


def background(state: dict, samples: dict) -> dict:
    """What the repairs did in [t0, t1], from the child's own record
    (and the program's counters where it has them)."""
    t0, t1 = samples["t0"], samples["t1"]
    passes = list(state["repair"].passes)
    ended = [p for p in passes if t0 <= p["end"] <= t1]
    in_flight = sum(max(0.0, min(t1, p["end"]) - max(t0, p["start"]))
                    for p in passes)
    # a pass still in flight at t1 has not reported: it began when the
    # last one's hashes and deletes were done
    last = max((p["end"] + p["gap_s"] for p in passes), default=t0)
    if not any(p["end"] > t1 for p in passes):
        in_flight += max(0.0, t1 - max(t0, last))
    counted = samples["counters"].get(REBUILT)
    by_passes = sum(
        state["pass_bytes"] * max(0.0, min(t1, p["end"]) - max(t0, p["start"]))
        / (p["end"] - p["start"]) for p in passes)
    rebuilt = counted if counted is not None else by_passes
    got = samples["counters"]
    count = got.get(f'{STAGE}_count{{stage="ec.rebuild"}}')

    def a_pass(stage: str) -> float | None:
        secs = got.get(f'{STAGE}_sum{{stage="{stage}"}}')
        return secs / count if secs is not None and count else None
    seconds = [p["end"] - p["start"] for p in ended]
    return {"passes_in_window": len(ended),
            "passes_before_window": sum(p["end"] < t0 for p in passes),
            "pass_s_median": statistics.median(seconds) if seconds else None,
            "pass_gap_s_median": statistics.median(
                p["gap_s"] for p in ended) if ended else None,
            # the program's split of a pass: copies pulled, the rebuild;
            # the rest of a pass is the plan, the mount and the drop
            "copy_s_a_pass": a_pass("ec.copy"),
            "rebuild_s_a_pass": a_pass("ec.rebuild"),
            "repairs_shed": sum(p["shed"] for p in ended),
            # more than one value: the rebuild changed its width, and
            # compiled, inside the window
            "batches_a_pass": sorted({
                b["probe"] - a["probe"] for a, b in zip(passes, passes[1:])
                if t0 <= b["end"] <= t1 and None not in (a["probe"],
                                                         b["probe"])}),
            "gap_share": 1.0 - in_flight / (t1 - t0),
            "rebuild_input_bytes": rebuilt,
            "rebuild_input_bytes_from": (
                "program_counter" if counted is not None else "passes"),
            "rebuild_input_bytes_by_passes": by_passes,
            "rebuild_rows_out": len(state["queued_lost"]),
            "queued_lost": state["queued_lost"],
            "warm": state["warm"]}


def summary(state: dict, samples: dict) -> dict:
    out = base.summary(state, samples)
    out["facts"].update(background(state, samples))
    return out


def verify(ctx: ops.Ctx, state: dict, samples: dict) -> dict:
    repair, queued = state["repair"], state["queued"]
    repair.stop()
    passes = list(repair.passes)
    # the plain reference over the kept source: the lost rows decoded
    # from the survivors and checked against the encoder's own
    ref = reference_rebuild.walk(queued.ref, ctx.k, ctx.m, ctx.large_block,
                                 ctx.small_block, state["queued_lost"])
    # the control: a rebuild that wrote the parity rows again
    control_differing = reference_warmdown.files_differing(
        ref["hashes"], ref["control_hashes"])
    pass_files = sum(reference_warmdown.files_differing(
        ref["hashes"], ref["control_hashes"] if ctx.control
        else p["hashes"]) for p in passes)
    elsewhere = sum(p["rebuilder"] != ctx.volume_url for p in passes)
    left = sum(p["files_after"] != state["queued_chip"]
               or p["partial_files"] > 0 for p in passes)
    untagged = sum(p["untagged"] for p in passes)
    # the queued volume's index on the chip's server and the live peers
    ecx = 0
    for d in [ctx.vdir] + [p.dir for p in state["peers"][1:]]:
        with open(os.path.join(d, os.path.basename(queued.base) + ".ecx"),
                  "rb") as f:
            ecx += int(f.read() != state["queued_ecx"])
    ops.drop_source(queued)
    out = base.verify(ctx, state, samples)  # the four-server cell's checks
    facts = background(state, samples)
    bg = ctx.traffic["background"]
    # `min_passes` is of the benchmark's window; a shorter one (a
    # rehearsal, a sweep's) is held to its share
    need = int(bg["min_passes"] * min(1.0, ctx.seconds / bg["window_s"]))
    facts.update(passes_verified=len(passes), min_passes=need,
                 control_pass_files_differing=control_differing,
                 admin_requests=sum(p["requests"] for p in passes),
                 decode_mismatches=ref["decode_mismatches"])
    out["facts"].update(facts)
    out["checks"] += [
        ops.check("pass_files_differing", pass_files, 0),
        ops.check("reference_decode_mismatches", ref["decode_mismatches"],
                  0),
        ops.check("passes_short", max(0, need - facts["passes_in_window"]),
                  0),
        ops.check("gap_share", round(facts["gap_share"], 4),
                  bg["max_gap_share"]),
        ops.check("passes_not_on_chip_server", elsewhere, 0),
        ops.check("passes_leaving_borrowed_copies", left, 0),
        ops.check("queued_ecx_files_differing", ecx, 0),
        ops.check("admin_requests_untagged", untagged, 0)]
    return out
