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
"""

from __future__ import annotations

import argparse
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


def ground_truth(cfg: dict, state: str, reference) -> None:
    """The query pool and its exact ground truth, from the kept copy of the
    rows, chunk by chunk; numpy only."""
    rows, dim = int(cfg["rows"]), int(cfg["dim"])
    store = open_rows(state, rows, dim)
    pool_n, k, seed = int(cfg["pool"]), int(cfg["k"]), int(cfg["data_seed"])
    picks = gen.pool_picks(seed, rows, pool_n)
    pool = np.asarray(store[np.sort(picks)])[np.argsort(np.argsort(picks))] \
        + gen.pool_noise(seed, pool_n, dim)
    topk = reference.TopK(cfg["distance"], pool, k)
    for first in range(0, rows, gen.CHUNK_ROWS):
        topk.update(first, np.asarray(store[first:first + gen.CHUNK_ROWS]))
    gt_ids, gt_dists = topk.result()
    np.save(os.path.join(state, "pool.npy"), pool.astype(np.float32))
    np.save(os.path.join(state, "gt_ids.npy"), gt_ids)
    np.save(os.path.join(state, "gt_dists.npy"), gt_dists)


def build(cfg: dict, state: str, expect_platform: str,
          spec_args: list[str]) -> dict:
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
        buckets = int(cfg["filter_buckets"])
        for first, chunk in gen.iter_chunks(int(cfg["data_seed"]), rows, dim):
            store[first:first + len(chunk)] = chunk
            for s in range(0, len(chunk), PUT_BATCH):
                errs = idx.put_batch([
                    StorObj(class_name=cls, uuid=gen.uuid_of(first + i),
                            properties={"bucket": (first + i) % buckets},
                            vector=chunk[i])
                    for i in range(s, min(s + PUT_BATCH, len(chunk)))])
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
    args = ap.parse_args(argv)
    spec = Spec(args.benchmark_json, args.extra_root)
    cfg = spec.config(args.config)
    if args.ground_truth:
        ground_truth(cfg, args.state, spec.reference(cfg["reference"]))
    else:
        build(cfg, args.state, args.expect_platform, spec.as_args())
    return 0


if __name__ == "__main__":
    sys.exit(main())
