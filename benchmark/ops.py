"""What run.py and the drivers share: a run's context, filling volumes
through the store's own writer, and the comparisons that decide `correct`.

`fill_store` runs in run.py's process (the volume server) before the load
generator starts; everything else runs in the load generator, a child
pinned to JAX_PLATFORMS=cpu. The client and the admin commands are the
product's (`seaweedfs_tpu.client`, `seaweedfs_tpu.shell.ec_commands`):
what `cli download` and `cli shell` run.
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass

import numpy as np

import datagen

from seaweedfs_tpu.client import Client, ClientError
from seaweedfs_tpu.shell.ec_commands import EcCommands


def emit(event: str, **fields) -> None:
    sys.stdout.write(json.dumps({"event": event, **fields}) + "\n")
    sys.stdout.flush()


def say(msg: str) -> None:
    emit("log", msg=msg)


@dataclass
class Volume:
    index: int
    vid: int
    base: str          # <vdir>/<collection>_<vid>
    ref: str           # hard links to the sealed .dat/.idx, for the reference
    position: np.ndarray  # item -> where it lies in the volume
    cookies: np.ndarray   # item -> cookie
    dat_bytes: int

    def fid(self, item: int) -> str:
        return (f"{self.vid},{int(self.position[item]) + 1:x}"
                f"{int(self.cookies[item]):08x}")


class Ctx:
    def __init__(self, raw: dict):
        self.raw = raw
        self.seed: int = raw["seed"]
        self.seconds: float = raw["seconds"]
        self.config: dict = raw["config"]
        self.traffic: dict = raw["traffic"]
        self.pop: dict = self.traffic["population"]
        self.collection: str = raw["collection"]
        self.volume_url: str = raw["volume"]
        self.vdir: str = raw["vdir"]
        self.work: str = raw["work"]
        self.control: bool = raw["control"]
        k, m = self.config["geometry"].split("+")
        self.k, self.m = int(k), int(m)
        self.large_block: int = self.config["large_block_bytes"]
        self.small_block: int = self.config["small_block_bytes"]
        self.size: int = self.config["object_bytes"]
        self.n_items: int = self.config["objects_per_volume"]
        self.client = Client(raw["master"])
        self.ec = EcCommands(self.client)
        self.deleted = datagen.deleted_items(self.pop, self.n_items)
        self.live = np.setdiff1d(np.arange(self.n_items), self.deleted)

    def admin(self, op: str, body: dict) -> dict:
        return self.client.volume_admin(self.volume_url, op, body)

    def order(self, index: int) -> np.ndarray:
        return datagen.placement(self.pop, self.seed, index, self.n_items,
                                 self.size, self.k, self.small_block)

    def volumes(self) -> list[Volume]:
        """The volumes `fill_store` wrote, as the master lists them."""
        vids = sorted(
            int(v["id"]) for node in self.client.dir_status()["nodes"]
            for v in node["volumes"]
            if v.get("collection", "") == self.collection)
        if len(vids) != self.pop["volumes"]:
            raise SystemExit(f"the master lists volumes {vids} of "
                             f"{self.collection!r}, wanted "
                             f"{self.pop['volumes']}")
        prefix = f"{self.collection}_" if self.collection else ""
        os.makedirs(os.path.join(self.work, "ref"), exist_ok=True)
        out = []
        for index, vid in enumerate(vids):
            position = np.empty(self.n_items, dtype=np.int64)
            position[self.order(index)] = np.arange(self.n_items)
            base = os.path.join(self.vdir, f"{prefix}{vid}")
            out.append(Volume(
                index, vid, base,
                os.path.join(self.work, "ref", f"{prefix}{vid}"), position,
                datagen.cookies(self.seed, index, self.n_items),
                os.path.getsize(base + ".dat")))
        return out


def room(config: dict, traffic: dict) -> tuple[int, int]:
    """(largest file, disk bytes) a run needs: each volume's .dat and its
    k+m shard files side by side, and slack."""
    k, m = (int(x) for x in config["geometry"].split("+"))
    dat = datagen.dat_bytes(config["objects_per_volume"],
                            config["object_bytes"])
    n_volumes = traffic["population"]["volumes"]
    return dat, int(n_volumes * dat * (1 + (k + m) / k) * 1.05) + (2 << 30)


def fill_store(store, ctx: Ctx) -> str:
    """Grow the mix's volumes in the cell's collection and write every
    object of each through `Store.write_needle`, the writer behind the
    volume server's PUT, in the order the seed gives; then delete the
    deleted items. In this process, because a million 1 KB PUTs over
    HTTP take longer than a run may (PR 24). Ends with everything on
    disk: a degraded read meets a volume that was written long ago.
    Returns a line for the log."""
    from seaweedfs_tpu.storage.needle import Needle
    t0 = time.time()
    n_volumes = ctx.pop["volumes"]
    ctx.client.grow(count=n_volumes, collection=ctx.collection)
    vids = sorted(v.vid for loc in store.locations
                  for v in loc.volumes.values()
                  if v.collection == ctx.collection)
    if len(vids) != n_volumes:
        raise SystemExit(f"asked the master for {n_volumes} volumes of "
                         f"{ctx.collection!r}, the store has {vids}")
    size = ctx.size
    want = datagen.dat_bytes(ctx.n_items, size)
    for index, vid in enumerate(vids):
        order = ctx.order(index)
        cookies = datagen.cookies(ctx.seed, index, ctx.n_items)
        bodies = bytearray(
            -(-ctx.n_items // datagen.BLOCK_ITEMS) * datagen.BLOCK_ITEMS
            * size)
        step = datagen.BLOCK_ITEMS * size
        for b in range(len(bodies) // step):
            bodies[b * step:(b + 1) * step] = datagen.block_bytes(
                ctx.seed, index, b, size)
        view = memoryview(bodies)
        for pos, item in enumerate(order.tolist()):
            store.write_needle(vid, Needle(
                cookie=int(cookies[item]), id=pos + 1,
                data=bytes(view[item * size:(item + 1) * size])))
        for item in ctx.deleted:
            pos = int(np.nonzero(order == item)[0][0])
            store.delete_needle(vid, Needle(cookie=int(cookies[item]),
                                            id=pos + 1))
        prefix = f"{ctx.collection}_" if ctx.collection else ""
        got = os.path.getsize(os.path.join(ctx.vdir, f"{prefix}{vid}.dat"))
        if not want <= got <= want + 64 * len(ctx.deleted) \
                or got % (ctx.k * ctx.small_block) == 0:
            raise SystemExit(f"volume {vid}: .dat is {got} bytes, wanted "
                             f"{want} and the tombstones, a ragged tail")
    os.sync()
    return (f"filled {n_volumes} volume(s), {ctx.n_items} objects of "
            f"{size} bytes each, in {time.time() - t0:.1f}s")


def keep_source(vol: Volume) -> None:
    """Encoding retires the source: keep its inodes for the reference
    through hard links (no bytes are copied)."""
    for ext in (".dat", ".idx"):
        os.link(vol.base + ext, vol.ref + ext)


def drop_source(vol: Volume) -> None:
    for ext in (".dat", ".idx"):
        if os.path.exists(vol.ref + ext):
            os.remove(vol.ref + ext)


def delete_shards(ctx: Ctx, vol: Volume, shard_ids: list[int]) -> None:
    ctx.admin("ec/delete_shards", {"volume_id": vol.vid,
                                   "collection": ctx.collection,
                                   "shard_ids": shard_ids})
    left = [s for s in shard_ids
            if os.path.exists(f"{vol.base}.ec{s:02d}")]
    if left:
        raise SystemExit(f"volume {vol.vid}: shards {left} were not removed")


def get_checked(client: Client, fid: str, want: bytes) -> bool:
    """GET one live item and compare its bytes with the seed's."""
    try:
        return client.download(fid) == want
    except ClientError:
        return False


def deleted_come_back(ctx: Ctx, vol: Volume) -> int:
    """How many deleted items a GET still returns."""
    back = 0
    for item in ctx.deleted:
        try:
            ctx.client.download(vol.fid(item))
            back += 1
        except ClientError:
            pass
    return back


def check(name: str, value, limit) -> dict:
    return {"name": name, "value": value, "limit": limit}


def all_within(checks: list[dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks)
