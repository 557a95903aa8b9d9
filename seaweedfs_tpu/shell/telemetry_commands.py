"""cluster.profile + cluster.tail: the cluster-wide faces of the
telemetry plane.

``cluster.profile`` fetches every node's always-on sampling profile
(/debug/pprof, observe/profiler.py) and merges the collapsed stacks into
one cluster-wide profile — identical stacks on different nodes sum, so
the hottest frames of the whole fleet top the output.

``cluster.tail`` fetches every node's wide-event ring (/debug/events,
observe/wideevents.py), keeps the slow tail (an explicit -minMs floor or
the p99 of what was fetched), attributes each slow request to its
dominant stage, and prints the ranked "where p99 goes" table — the
question every perf round starts with.
"""

from __future__ import annotations

import json
import urllib.parse
import urllib.request
from concurrent.futures import ThreadPoolExecutor

from ..observe import wideevents
from .commands import CommandEnv, command, parser


def _targets(env: CommandEnv, extra: list[str]) -> list[str]:
    """master + every registered volume server + the shell's filer + any
    -node extras, de-duplicated in order (same discovery cluster.trace
    uses)."""
    targets = [env.client.master]
    try:
        with urllib.request.urlopen(
                f"http://{env.client.master}/vol/list", timeout=10) as r:
            for node in json.load(r).get("nodes", []):
                if node.get("url"):
                    targets.append(node["url"])
    except Exception:
        pass  # master down: still query filer/-node extras
    if env.filer:
        targets.append(env.filer)
    targets.extend(extra)
    return list(dict.fromkeys(targets))


def _fetch(url: str, path: str, timeout: float = 10.0) -> tuple[str, str]:
    """(body, error) — a dead/denied node must not hide the rest of the
    cluster; the failure is surfaced per-node in the command output."""
    try:
        with urllib.request.urlopen(f"http://{url}{path}",
                                    timeout=timeout) as r:
            return r.read().decode("utf-8", "replace"), ""
    except Exception as e:
        return "", str(e)


@command("cluster.profile",
         "merge the always-on sampling profiles of every node into one "
         "collapsed-stack profile (cluster.profile [-class fg|bg|system"
         "|idle] [-node host:port]... [-output profile.folded])")
def cluster_profile(env: CommandEnv, argv: list[str]):
    p = parser("cluster.profile")
    p.add_argument("-class", dest="cls", default="",
                   help="only samples of one priority class")
    p.add_argument("-node", action="append", default=[],
                   help="extra nodes to query (S3/webdav gateways)")
    p.add_argument("-output", default="",
                   help="write the merged collapsed stacks to this file")
    args = p.parse_args(argv)

    urls = _targets(env, args.node)
    qs = "?format=collapsed"
    if args.cls:
        qs += "&class=" + urllib.parse.quote(args.cls)
    with ThreadPoolExecutor(max_workers=min(16, len(urls))) as pool:
        results = list(pool.map(lambda u: _fetch(u, f"/debug/pprof{qs}"),
                                urls))

    merged: dict[str, int] = {}
    queried = []
    for url, (body, err) in zip(urls, results):
        entry: dict = {"node": url}
        if err:
            entry["error"] = err
            queried.append(entry)
            continue
        n = 0
        for line in body.splitlines():
            stack, _, count = line.rpartition(" ")
            if not stack or not count.isdigit():
                continue
            merged[stack] = merged.get(stack, 0) + int(count)
            n += int(count)
        entry["samples"] = n
        queried.append(entry)

    rows = sorted(merged.items(), key=lambda kv: -kv[1])
    text = "".join(f"{stack} {count}\n" for stack, count in rows)
    out = {"nodes": queried, "distinct_stacks": len(rows),
           "total_samples": sum(merged.values())}
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
        out["output"] = args.output
    else:
        out["profile"] = text
    return out


def _shares(parts: list, places: int = 3) -> list[float]:
    """Each part's share of their sum to `places` decimals, and still
    summing to 1: every share rounded down, the units of the last place
    left over handed to the largest remainders (three equal buckets read
    0.334 0.333 0.333, not 0.333 three times)."""
    scale = 10 ** places
    total = sum(parts)
    if total <= 0:
        return [0.0] * len(parts)
    exact = [p * scale / total for p in parts]
    units = [int(x) for x in exact]
    left = scale - sum(units)
    by_remainder = sorted(range(len(parts)),
                          key=lambda i: units[i] - exact[i])
    for i in by_remainder[:left]:
        units[i] += 1
    return [u / scale for u in units]


@command("cluster.tail",
         "rank where the cluster's tail latency goes by dominant stage "
         "(cluster.tail [-minMs N] [-pct 99] [-limit N] [-class fg|bg] "
         "[-node host:port]...)")
def cluster_tail(env: CommandEnv, argv: list[str]):
    p = parser("cluster.tail")
    p.add_argument("-minMs", type=float, default=0.0,
                   help="explicit slow floor; 0 = use -pct of the fetch")
    p.add_argument("-pct", type=float, default=99.0,
                   help="tail percentile when -minMs is not given")
    p.add_argument("-limit", type=int, default=2000,
                   help="events to fetch per node")
    p.add_argument("-class", dest="cls", default="",
                   help="only requests of one priority class")
    p.add_argument("-node", action="append", default=[])
    args = p.parse_args(argv)

    urls = _targets(env, args.node)
    q = {"limit": str(args.limit)}
    if args.cls:
        q["class"] = args.cls
    qs = "?" + urllib.parse.urlencode(q)
    with ThreadPoolExecutor(max_workers=min(16, len(urls))) as pool:
        results = list(pool.map(lambda u: _fetch(u, f"/debug/events{qs}"),
                                urls))

    events: list[dict] = []
    queried = []
    for url, (body, err) in zip(urls, results):
        entry: dict = {"node": url}
        if err:
            entry["error"] = err
            queried.append(entry)
            continue
        try:
            got = json.loads(body).get("events", [])
        except ValueError:
            entry["error"] = "bad json"
            queried.append(entry)
            continue
        entry["events"] = len(got)
        queried.append(entry)
        for e in got:
            e["_node"] = url
            events.append(e)

    # in-process test clusters share one ring: de-dup by (trace, ts,
    # name) so one request isn't counted once per queried node
    seen: set[tuple] = set()
    uniq = []
    for e in events:
        key = (e.get("trace"), e.get("ts"), e.get("name"),
               e.get("dur_us"))
        if key in seen:
            continue
        seen.add(key)
        uniq.append(e)
    events = uniq

    if args.minMs > 0:
        threshold_us = args.minMs * 1000.0
    elif events:
        durs = sorted(e.get("dur_us", 0) for e in events)
        rank = min(len(durs) - 1,
                   max(0, int(len(durs) * args.pct / 100.0)))
        threshold_us = durs[rank]
    else:
        threshold_us = 0.0
    slow = [e for e in events if e.get("dur_us", 0) >= threshold_us]

    # attribute each slow request to its single dominant stage, then
    # rank buckets by total attributed time: the table reads "the tail
    # is disk-bound / queue-bound / lock-bound ..."
    buckets: dict[str, dict] = {}
    for e in slow:
        name, us = wideevents.dominant_stage(e)
        bucket = ("handler" if name == "(handler)"
                  else wideevents.stage_bucket(name))
        b = buckets.setdefault(bucket, {
            "bucket": bucket, "count": 0, "total_us": 0, "stages": {},
            "example_trace": "", "example_node": "", "example_us": 0})
        b["count"] += 1
        b["total_us"] += us
        b["stages"][name] = b["stages"].get(name, 0) + 1
        if e.get("dur_us", 0) >= b["example_us"]:
            b["example_us"] = e.get("dur_us", 0)
            b["example_trace"] = e.get("trace", "")
            b["example_node"] = e.get("_node", "")
    ranked = sorted(buckets.values(), key=lambda b: -b["total_us"])
    shares = _shares([b["total_us"] for b in ranked])
    table = []
    for b, share in zip(ranked, shares):
        top_stages = sorted(b["stages"].items(), key=lambda kv: -kv[1])
        table.append({
            "stage": b["bucket"],
            "count": b["count"],
            "total_ms": round(b["total_us"] / 1000.0, 2),
            "share": share,
            "top_stages": [s for s, _ in top_stages[:3]],
            "example_trace": b["example_trace"],
            "example_node": b["example_node"],
        })
    return {"nodes": queried, "events_considered": len(events),
            "slow_count": len(slow),
            "threshold_ms": round(threshold_us / 1000.0, 2),
            "by_stage": table}
