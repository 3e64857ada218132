"""Build child: write a configuration's rows into a state directory.

    python -m benchmarks.build --config <name> --state <dir> [--expect-platform tpu]

Owns the chip while it runs and exits before the server starts. Creates the
class through `app.schema.add_class`, makes the rows from the configuration's
data seed chunk by chunk, writes them through `class_index.put_batch` in
batches of 10,000 (the write path below the REST handler: LSM, inverted
index, vector log, device add) and shuts the App down cleanly. While the App
flushes, a second child that never touches JAX computes the exact ground
truth of the query pool with the configuration's plain reference, from the
copy of the rows kept beside the data. The manifest is written last: a
directory without one is not a state directory.

What rows carry beside their vector, the filter each pool query carries and
which rows a filter allows are the configuration's dataset's to say
(benchmarks/datasets/). A filter plan is one `where` (or none) a pool query;
a state directory keeps the ground truth of every plan it has been asked
for: the plan without a filter as `gt_ids.npy` / `gt_dists.npy`, any other
as `plan-<hash of its filters>.npz` beside its filters in
`plan-<hash>.json`, computed once by the same numpy child
(`--ground-truth --traffic <mix>`) the first time a traffic mix asks for it.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from benchmarks.lib import data as gen
from benchmarks.lib.server import child_env, dir_bytes
from benchmarks.lib.spec import ROOT, Spec

PUT_BATCH = 10_000
MANIFEST = "manifest.json"


def manifest_want(cfg: dict) -> dict:
    """What a state directory's manifest must say to serve this config."""
    return {"config": cfg["name"], "config_sha256": cfg["_sha256"],
            "data_seed": int(cfg["data_seed"]), "rows": int(cfg["rows"]),
            "dim": int(cfg["dim"]), "pool": int(cfg["pool"]),
            "k": int(cfg["k"])}


def read_manifest(state: str) -> dict | None:
    try:
        with open(os.path.join(state, MANIFEST), "rb") as f:
            return json.loads(f.read())
    except (OSError, ValueError):
        return None


def state_matches(state: str, cfg: dict) -> bool:
    m = read_manifest(state)
    return m is not None and all(m.get(k) == v
                                 for k, v in manifest_want(cfg).items())


def open_rows(state: str, rows: int, dim: int, mode: str = "r"):
    return np.memmap(os.path.join(state, "rows.f32"), np.float32, mode,
                     shape=(rows, dim))


def plan_filters(cfg: dict, traffic: dict | None, dataset) -> list:
    """The `where` (or None) of each pool query under a traffic mix: the
    mix's own constant `where`, else the plan of the dataset's it names
    (`filter_plan`; "none" is no filter whatever the dataset), else what the
    dataset's queries carry by themselves."""
    traffic = traffic or {}
    if traffic.get("where"):
        return [traffic["where"]] * int(cfg["pool"])
    if traffic.get("filter_plan") == "none":
        return [None] * int(cfg["pool"])
    filters = list(dataset.filter_plan(cfg, traffic.get("filter_plan")))
    if len(filters) != int(cfg["pool"]):
        raise ValueError(f"{len(filters)} filters for a pool of {cfg['pool']}")
    return filters


def distinct_filters(filters: list) -> tuple[list, np.ndarray]:
    """(the distinct filters, which of them each entry of `filters` is):
    a dataset reads each distinct filter once, whatever asks."""
    texts = [json.dumps(w, sort_keys=True) for w in filters]
    distinct = {t: i for i, t in enumerate(sorted(set(texts)))}
    return ([json.loads(t) for t in distinct],
            np.array([distinct[t] for t in texts], np.int64))


def allowed_pairs(dataset, cfg: dict, filters: list, queries, rows):
    """bool per (pool query, row id) pair: does the query's own filter
    allow the row, by the dataset's reading. Each distinct filter and each
    distinct row is read once."""
    wheres, which = distinct_filters([filters[int(q)] for q in queries])
    urows, rix = np.unique(np.asarray(rows, np.int64), return_inverse=True)
    return dataset.allowed(cfg, wheres, urows)[which, rix]


def plan_files(state: str, filters: list) -> tuple[str, ...]:
    """Where a plan's ground truth lives: (ids, dists) of the plan without
    a filter, (npz, filters) of any other."""
    if all(w is None for w in filters):
        return (os.path.join(state, "gt_ids.npy"),
                os.path.join(state, "gt_dists.npy"))
    digest = hashlib.sha256(
        json.dumps(filters, sort_keys=True).encode()).hexdigest()[:16]
    return (os.path.join(state, f"plan-{digest}.npz"),
            os.path.join(state, f"plan-{digest}.json"))


def load_truth(state: str, filters: list):
    """-> (gt_ids [pool, k] padded with -1, rows each query is allowed,
    None where no query has a filter), or None if the state directory does
    not hold this plan's ground truth yet."""
    files = plan_files(state, filters)
    if not os.path.isfile(files[0]):
        return None
    if files[0].endswith(".npy"):
        return np.load(files[0]), None
    with np.load(files[0]) as z:
        return z["ids"], z["allowed"]


def _save(path: str, write) -> None:
    """`path` appears whole or not at all. The temporary keeps the suffix:
    numpy appends one to a name that lacks it."""
    tmp = os.path.join(os.path.dirname(path), "tmp-" + os.path.basename(path))
    write(tmp)
    os.replace(tmp, path)


def ground_truth(cfg: dict, state: str, reference, dataset,
                 filters: list) -> None:
    """The query pool and the exact ground truth of one filter plan, from
    the kept copy of the rows, chunk by chunk; numpy only."""
    rows, dim = int(cfg["rows"]), int(cfg["dim"])
    store = open_rows(state, rows, dim)
    pool_n, k, seed = int(cfg["pool"]), int(cfg["k"]), int(cfg["data_seed"])
    picks = gen.pool_picks(seed, rows, pool_n)
    pool = np.asarray(store[np.sort(picks)])[np.argsort(np.argsort(picks))] \
        + gen.pool_noise(seed, pool_n, dim)
    wheres, which = distinct_filters(filters)
    filtered = any(w is not None for w in filters)
    topk = reference.TopK(cfg["distance"], pool, k)
    for first in range(0, rows, gen.CHUNK_ROWS):
        chunk = np.asarray(store[first:first + gen.CHUNK_ROWS])
        mask = None
        if filtered:
            mask = dataset.allowed(
                cfg, wheres, np.arange(first, first + len(chunk)))[which]
        topk.update(first, chunk, mask)
    gt_ids, gt_dists = topk.result()
    pool_path = os.path.join(state, "pool.npy")
    if not os.path.isfile(pool_path):
        _save(pool_path, lambda p: np.save(p, pool.astype(np.float32)))
    files = plan_files(state, filters)
    if filtered:
        with open(files[1], "w") as f:
            json.dump({"filters": filters}, f)
        _save(files[0], lambda p: np.savez(
            p, ids=gt_ids, dists=gt_dists, allowed=topk.allowed))
    else:
        _save(files[1], lambda p: np.save(p, gt_dists))
        _save(files[0], lambda p: np.save(p, gt_ids))


def build(cfg: dict, state: str, expect_platform: str,
          spec_args: list[str], dataset) -> dict:
    t0 = time.monotonic()
    from weaviate_tpu import device

    device.enable_compile_cache()
    ident = device.identity()
    print(f"[build] device {ident}", flush=True)
    if ident["platform"] != expect_platform or \
            ident["count"] < int(cfg["chips"]):
        print(f"[build] runs on {ident['count']} x {ident['platform']}, want "
              f"{cfg['chips']} x {expect_platform}: no accelerator, no build",
              file=sys.stderr, flush=True)
        raise SystemExit(3)

    from weaviate_tpu.config import load_config
    from weaviate_tpu.entities.storobj import StorObj
    from weaviate_tpu.server import App

    rows, dim, cls = int(cfg["rows"]), int(cfg["dim"]), cfg["class"]["class"]
    shutil.rmtree(state, ignore_errors=True)
    os.makedirs(state)
    app = App(config=load_config(), data_path=os.path.join(state, "data"))
    acked = 0
    truth = None
    try:
        app.schema.add_class(dict(cfg["class"]))
        idx = app.db.get_index(cls)
        store = open_rows(state, rows, dim, "w+")
        for first, chunk in gen.iter_chunks(int(cfg["data_seed"]), rows, dim):
            store[first:first + len(chunk)] = chunk
            for s in range(0, len(chunk), PUT_BATCH):
                e = min(s + PUT_BATCH, len(chunk))
                props = dataset.properties(
                    cfg, np.arange(first + s, first + e))
                errs = idx.put_batch([
                    StorObj(class_name=cls, uuid=gen.uuid_of(first + i),
                            properties=props[i - s], vector=chunk[i])
                    for i in range(s, e)])
                bad = [e for e in errs if e is not None]
                if bad:
                    raise RuntimeError(f"put_batch at row {first + s}: "
                                       f"{bad[0]!r}")
                acked += len(errs)
            print(f"[build] {acked}/{rows} rows acknowledged, "
                  f"{time.monotonic() - t0:.0f}s", flush=True)
        store.flush()
        del store
        # the ground truth needs only the kept copy of the rows: a child that
        # never touches JAX computes it while the App flushes and shuts down
        truth = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.build", "--config",
             cfg["name"], "--state", state, "--ground-truth"] + spec_args,
            cwd=ROOT, env=child_env())
        t1 = time.monotonic()
        app.shutdown()
        print(f"[build] clean shutdown in {time.monotonic() - t1:.1f}s",
              flush=True)
        put_s = time.monotonic() - t0
        if truth.wait() != 0:
            raise RuntimeError(f"ground-truth child exited {truth.returncode}")
        gt_s = time.monotonic() - t0 - put_s     # what it added to the build
    except BaseException:
        if truth is not None and truth.poll() is None:
            truth.kill()
            truth.wait()
        app.shutdown()
        raise

    manifest = manifest_want(cfg)
    manifest.update({
        "acknowledged": acked, "put_seconds": round(put_s, 1),
        "ground_truth_seconds": round(gt_s, 1),
        "disk_bytes": dir_bytes(state), "device": ident})
    tmp = os.path.join(state, MANIFEST + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=1)
    os.replace(tmp, os.path.join(state, MANIFEST))
    # gigabytes of dirty pages would be written back under the first window
    os.sync()
    print(f"[build] done: {json.dumps(manifest)}", flush=True)
    return manifest


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--state", required=True)
    ap.add_argument("--expect-platform", default="tpu")
    ap.add_argument("--benchmark-json", default=None)
    ap.add_argument("--extra-root", default=None)
    ap.add_argument("--ground-truth", action="store_true",
                    help="only the pool and its ground truth, from the rows "
                         "already in --state (the build starts this itself)")
    ap.add_argument("--traffic", default=None,
                    help="with --ground-truth: the traffic mix whose filter "
                         "plan to compute (default: the dataset's own)")
    args = ap.parse_args(argv)
    spec = Spec(args.benchmark_json, args.extra_root)
    cfg = spec.config(args.config)
    dataset = spec.dataset(cfg)
    if args.ground_truth:
        traffic = spec.traffic(args.traffic) if args.traffic else None
        ground_truth(cfg, args.state, spec.reference(cfg["reference"]),
                     dataset, plan_filters(cfg, traffic, dataset))
    else:
        build(cfg, args.state, args.expect_platform, spec.as_args(), dataset)
    return 0


if __name__ == "__main__":
    sys.exit(main())
