"""Degraded GETs of objects wider than a tile in an open loop:
`open_loop_get`'s volume, loss, schedule, window, samples and checks,
letter for letter, over the configuration's 64 KB objects.

An interval of such an object is dispatched at one of several widths
(the server's host call rounds it up to a power of two of tiles), and
each width is a program of its own. This driver adds what that needs
before the clock starts: it waits until the server says that its widths
are compiled, where the server says so (`/admin/ec/mesh_status`,
`coder.resolved[].warm`; a program that does not say is not waited
for), and sends one checked GET for each size class of lost interval
that the schedule holds, so that a program that compiles inside a GET
has compiled every width before the window. Both are set-up.
"""

from __future__ import annotations

import json
import time
import urllib.request

import datagen
import ops
import reference
from run import load_module

base = load_module("drivers", "open_loop_get")

WARM_LIMIT_S = 120  # the server's own warm-up, begun at its first encode

window = base.window
summary = base.summary


def size_class(size: int) -> int:
    """Intervals of one class pad to one width under any rounding to a
    power of two (below the least width there are classes to spare)."""
    return (size - 1).bit_length()


def lost_classes(ctx: ops.Ctx, vol: ops.Volume, items: list[int],
                 lost: list[int]) -> dict[int, int]:
    """size class -> an item with a lost interval of it: the intervals
    of the whole record (header and trailer too: they are what the
    server reads), by the reference's own locate over the sealed .idx."""
    with open(vol.ref + ".idx", "rb") as f:
        keys, offsets, _ = reference.fold_idx(f.read())
    out: dict[int, int] = {}
    record = datagen.record_bytes(ctx.size)
    for item in items:
        at = int(offsets[keys.searchsorted(int(vol.position[item]) + 1)])
        for shard, _, size in reference.locate(
                at * 8, record, vol.dat_bytes, ctx.k, ctx.large_block,
                ctx.small_block):
            if shard in lost:
                out.setdefault(size_class(size), item)
    return out


def server_warm(ctx: ops.Ctx) -> list[dict]:
    """What each coder of the server says of its warm-up; nothing for a
    coder that says nothing (a host coder, a parent commit)."""
    with urllib.request.urlopen(
            f"http://{ctx.volume_url}/admin/ec/mesh_status",
            timeout=30) as r:
        resolved = json.loads(r.read())["coder"]["resolved"]
    return [d["warm"] for d in resolved if "warm" in d]


def wait_for_server(ctx: ops.Ctx) -> dict:
    t0 = time.time()
    while True:
        warm = server_warm(ctx)
        if all(w["state"] not in ("idle", "running") for w in warm):
            return {"waited_s": time.time() - t0, "reported": warm}
        if time.time() - t0 > WARM_LIMIT_S:
            raise SystemExit(f"the server's warm-up has not ended after "
                             f"{WARM_LIMIT_S} s: {warm}")
        time.sleep(0.05)


def warm_classes(ctx: ops.Ctx, state: dict) -> None:
    """One checked GET for each size class of lost interval that the
    schedule holds and no earlier schedule of this run held."""
    classes = lost_classes(ctx, state["vol"], sorted(state["want"]),
                           state["lost"])
    client = state["clients"].get()
    for cls, item in sorted(classes.items()):
        if cls not in state["warmed"]:
            state["warmed"][cls] = item
            state["warm_wrong"] += int(not ops.get_checked(
                client, state["fids"][item], state["bodies"][item]))
    state["clients"].put(client)


def plan(ctx: ops.Ctx, state: dict, rate: float) -> None:
    """`open_loop_get.plan`, and the new schedule's classes warmed (a
    sweep plans a window a rate)."""
    base.plan(ctx, state, rate)
    warm_classes(ctx, state)


def setup(ctx: ops.Ctx) -> dict:
    state = base.setup(ctx)
    state["server_warm"] = wait_for_server(ctx)
    state["warmed"] = {}
    warm_classes(ctx, state)
    return state


def verify(ctx: ops.Ctx, state: dict, samples: dict) -> dict:
    out = base.verify(ctx, state, samples)
    reported = state["server_warm"]["reported"]
    out["facts"].update(
        warm_wait_s=state["server_warm"]["waited_s"],
        server_warm=reported,
        # size class (an interval of up to 2**class bytes) -> the item
        # whose GET warmed it
        classes_warmed={str(c): i for c, i in state["warmed"].items()})
    out["checks"].append(ops.check(
        "server_warmups_not_done",
        sum(w["state"] != "done" for w in reported), 0))
    return out
