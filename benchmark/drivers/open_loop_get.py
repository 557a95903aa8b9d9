"""Degraded GETs in an open loop: one EC volume with shards lost, requests
due at the schedule's instants whatever the server does, each timed from
when it was due to when its last byte was checked."""

from __future__ import annotations

import hashlib
import queue
import statistics
import time
from concurrent.futures import ThreadPoolExecutor

import datagen
import ops
import reference
from seaweedfs_tpu.client import Client, ClientError


def digest(data: bytes) -> bytes:
    return hashlib.blake2b(data, digest_size=16).digest()


def lost_ranges(ctx: ops.Ctx, vol: ops.Volume, items: set[int],
                lost: list[int]) -> dict[int, list[tuple[int, int]]]:
    """item -> [(offset in the body, size)] of the parts of its body that
    lie on a lost shard, by the reference's own locate over the sealed
    .idx."""
    with open(vol.ref + ".idx", "rb") as f:
        keys, offsets, _ = reference.fold_idx(f.read())
    out = {}
    for item in items:
        at = int(offsets[keys.searchsorted(int(vol.position[item]) + 1)])
        pos, ranges = 0, []
        for shard, _, size in reference.locate(
                at * 8 + 20, ctx.size,  # header 16, body size 4
                vol.dat_bytes, ctx.k, ctx.large_block, ctx.small_block):
            if shard in lost:
                ranges.append((pos, size))
            pos += size
        out[item] = ranges
    return out


def plan(ctx: ops.Ctx, state: dict, rate: float) -> None:
    """The window's schedule at `rate`, and what each answer has to be."""
    vol = state["vol"]
    load = {**ctx.traffic["load"], "rate_per_s": rate}
    due, items = datagen.zipf_schedule(ctx.pop, load, ctx.live,
                                       ctx.seconds, ctx.seed)
    asked = sorted(set(items))
    body = datagen.Bodies(ctx.seed, vol.index, ctx.size)
    bodies = {i: body(i) for i in asked}
    state.update(
        due=due, items=items, bodies=bodies,
        ranges=lost_ranges(ctx, vol, set(asked), state["lost"]),
        want={i: digest(b) for i, b in bodies.items()},
        fids={i: vol.fid(i) for i in asked})


def setup(ctx: ops.Ctx) -> dict:
    load = ctx.traffic["load"]
    vol = ctx.volumes()[0]
    ops.keep_source(vol)
    ctx.ec.encode_many([vol.vid], ctx.collection)
    lost = datagen.lost_shards(ctx.seed, vol.index, ctx.k, ctx.m,
                               load["lost_data"], load["lost_parity"])
    ops.delete_shards(ctx, vol, lost)
    state = {"vol": vol, "lost": lost}
    plan(ctx, state, load["rate_per_s"])
    # every client of the pool has found the volume, holds an open
    # connection and has read from a lost shard before the clock starts:
    # the one width a 1 KB interval pads to meets no compile in the
    # window (the matrix is an operand, so every loss pattern shares it)
    asked = sorted(state["want"])
    degraded = [i for i in asked if state["ranges"][i]]
    clients: queue.SimpleQueue = queue.SimpleQueue()
    warm_wrong = 0
    for n in range(load["client_threads"]):
        client = Client(ctx.raw["master"])
        for i in (degraded[n % len(degraded)], asked[n % len(asked)]):
            warm_wrong += int(not ops.get_checked(
                client, state["fids"][i], state["bodies"][i]))
        clients.put(client)
    state.update(warm_wrong=warm_wrong, clients=clients)
    return state


def window(ctx: ops.Ctx, state: dict) -> dict:
    fids, want = state["fids"], state["want"]
    n = len(state["due"])
    latency = [None] * n
    late = [0.0] * n
    ok = [False] * n
    clients = state["clients"]
    t0 = time.perf_counter()

    def one(i: int, due: float, item: int) -> None:
        client = clients.get()
        late[i] = time.perf_counter() - t0 - due
        try:
            ok[i] = digest(client.download(fids[item])) == want[item]
        except ClientError:
            ok[i] = False
        latency[i] = time.perf_counter() - t0 - due
        clients.put(client)

    with ThreadPoolExecutor(
            max_workers=ctx.traffic["load"]["client_threads"]) as ex:
        futures = []
        for i, (due, item) in enumerate(zip(state["due"], state["items"])):
            wait = due - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            futures.append(ex.submit(one, i, due, item))
        for f in futures:
            f.result()
    return {"seconds": time.perf_counter() - t0, "latency": latency,
            "late": late, "ok": ok}


def percentile(values: list[float], q: float) -> float:
    s = sorted(values)
    return s[min(len(s) - 1, int(q * len(s)))]


def summary(state: dict, samples: dict) -> dict:
    """The window's metrics and facts, from its samples alone."""
    lat_ms = [1e3 * x for x in samples["latency"]]
    touched = [bool(state["ranges"][i]) for i in state["items"]]
    half = len(lat_ms) // 2
    return {
        "metrics": {
            "get_p50_ms": {"value": statistics.median(lat_ms),
                           "unit": "ms"}},
        "facts": {"gets_completed": len(lat_ms),
                  # the tail follows how often the machine freezes in
                  # the window (PERF.md): a diagnosis, not a metric
                  "get_p95_ms": percentile(lat_ms, 0.95),
                  "window_s": samples["seconds"],
                  "late_p95_ms": 1e3 * percentile(samples["late"], 0.95),
                  "late_max_ms": 1e3 * max(samples["late"]),
                  "get_p99_ms": percentile(lat_ms, 0.99),
                  "get_max_ms": max(lat_ms),
                  # what a GET costs by kind (a diagnosis, not a metric)
                  "plain_p50_ms": statistics.median(
                      x for x, t in zip(lat_ms, touched) if not t),
                  "degraded_p50_ms": statistics.median(
                      x for x, t in zip(lat_ms, touched) if t),
                  # a backlog that grows shows as a slower second half
                  "p50_first_half_ms": statistics.median(lat_ms[:half]),
                  "p50_second_half_ms": statistics.median(lat_ms[half:]),
                  "p95_first_half_ms": percentile(lat_ms[:half], 0.95),
                  "p95_second_half_ms": percentile(lat_ms[half:], 0.95),
                  "gets_on_lost_shards": sum(touched),
                  "intervals_expected": sum(
                      len(state["ranges"][i]) for i in state["items"]),
                  # for the kernel's roofline: the columns the window's
                  # reconstructions had to produce, one row out each
                  "columns_coded": sum(
                      size for i in state["items"]
                      for _, size in state["ranges"][i]),
                  "rows_out": 1,
                  "distinct_items": len(state["want"]),
                  "lost": state["lost"]}}


def verify(ctx: ops.Ctx, state: dict, samples: dict) -> dict:
    vol = state["vol"]
    wrong = sum(not x for x in samples["ok"])
    # the control answers the window's GETs without reconstructing:
    # bytes on a lost shard come back as zeros. Every run reads how many
    # GETs it would have failed; under --control it stands in the
    # program's place
    answer = {}
    for item, body in state["bodies"].items():
        body = bytearray(body)
        for pos, size in state["ranges"][item]:
            body[pos:pos + size] = bytes(size)
        answer[item] = digest(bytes(body)) == state["want"][item]
    control_wrong = sum(not answer[item] for item in state["items"])
    if ctx.control:
        wrong = control_wrong
    back = ops.deleted_come_back(ctx, vol)
    with open(vol.ref + ".idx", "rb") as f:
        ecx_want = reference.sorted_ecx(f.read())
    with open(vol.base + ".ecx", "rb") as f:
        ecx = int(f.read() != ecx_want)
    ops.drop_source(vol)
    out = summary(state, samples)
    out["facts"]["control_gets_wrong"] = control_wrong
    return {
        **out,
        "attempted": len(samples["ok"]), "failed": wrong,
        "checks": [ops.check("gets_wrong", wrong, 0),
                   ops.check("warmup_gets_wrong", state["warm_wrong"], 0),
                   ops.check("deleted_needles_back", back, 0),
                   ops.check("ecx_files_differing", ecx, 0)]}
