#!/usr/bin/env python
"""Input-pipeline throughput benchmark.

The reference shipped the harness designs (ImageRecordIter, tools/
bandwidth) but never committed data-pipeline numbers (SURVEY §6). This
measures the stages that feed the chip, host-side, so regressions in
the IO path are visible without TPU time:

  1. RecordIO sequential read — native C++ reader (libmxtpu_io.so) vs
     the pure-python reader, records/s and MB/s.
  2. Threaded prefetcher gain — native reader behind the C++ prefetch
     queue vs direct iteration, on a decode+augment consumer (the
     overlap the reference's PrefetcherIter provided).
  3. gluon DataLoader — samples/s over a JPEG dataset with the standard
     train transform (RandomResizedCrop + flip + ToTensor + Normalize),
     single-process vs multiworker.

Prints one JSON object; `--output` also writes it to a file
(results committed as benchmark/results_io_cpu.json).

CLI: python benchmark/io_bench.py [--records 2000] [--jpegs 600]
     [--workers 4] [--output out.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as onp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def log(*a):
    print("[io_bench]", *a, file=sys.stderr, flush=True)


def bench_recordio(n_records: int, payload: int, tmp: str):
    """Native vs python sequential read of the same .rec file."""
    import ctypes

    from mxnet_tpu import _native, recordio

    path = os.path.join(tmp, "seq.rec")
    rs = onp.random.RandomState(0)
    payloads = [rs.bytes(payload) for _ in range(n_records)]
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()
    total_mb = n_records * payload / 1e6

    def timed(read_all):
        t0 = time.perf_counter()
        count = read_all()
        dt = time.perf_counter() - t0
        assert count == n_records
        return dt

    def py_read():
        r = recordio.MXRecordIO(path, "r")  # pure-python reader
        n = 0
        while r.read() is not None:
            n += 1
        r.close()
        return n

    nat = _native.lib()

    def native_read():
        h = nat.MXTRecordIOReaderCreate(path.encode())
        assert h
        data = ctypes.c_char_p()
        size = ctypes.c_uint64()
        n = 0
        while nat.MXTRecordIOReaderNext(
                h, ctypes.byref(data), ctypes.byref(size)) == 0:
            ctypes.string_at(data, size.value)
            n += 1
        nat.MXTRecordIOReaderFree(h)
        return n

    if nat is None:
        log("native io library unavailable; skipping native rows")
        dt = min(timed(py_read) for _ in range(3))
        return {"records": n_records, "payload_bytes": payload,
                "python_rec_s": round(n_records / dt, 1),
                "python_mb_s": round(total_mb / dt, 1)}, path

    py_dt = min(timed(py_read) for _ in range(3))
    nat_dt = min(timed(native_read) for _ in range(3))
    rec = {
        "records": n_records, "payload_bytes": payload,
        "python_rec_s": round(n_records / py_dt, 1),
        "python_mb_s": round(total_mb / py_dt, 1),
        "native_rec_s": round(n_records / nat_dt, 1),
        "native_mb_s": round(total_mb / nat_dt, 1),
        "native_speedup": round(py_dt / nat_dt, 2),
    }
    log(f"recordio: native {rec['native_mb_s']} MB/s vs python "
        f"{rec['python_mb_s']} MB/s ({rec['native_speedup']}x)")
    return rec, path


def bench_prefetcher(path: str, n_records: int):
    """Prefetch overlap: consumer does real work per record (decode-ish
    numpy crunch); the C++ prefetch thread should hide read latency."""
    import ctypes

    from mxnet_tpu import _native, recordio

    def consume(buf):
        a = onp.frombuffer(buf, onp.uint8)[:65536].astype(onp.float32)
        return float(a.sum())

    nat = _native.lib()
    if nat is None:
        return {"skipped": "native io library unavailable"}

    def direct():
        h = nat.MXTRecordIOReaderCreate(path.encode())
        data = ctypes.c_char_p()
        size = ctypes.c_uint64()
        t0 = time.perf_counter()
        n = 0
        while nat.MXTRecordIOReaderNext(
                h, ctypes.byref(data), ctypes.byref(size)) == 0:
            consume(ctypes.string_at(data, size.value))
            n += 1
        dt = time.perf_counter() - t0
        nat.MXTRecordIOReaderFree(h)
        assert n == n_records
        return dt

    def prefetched():
        pf = recordio.ThreadedRecordReader(path, capacity=64)
        assert pf.is_native
        t0 = time.perf_counter()
        n = 0
        for buf in pf:
            consume(buf)
            n += 1
        dt = time.perf_counter() - t0
        pf.close()
        assert n == n_records
        return dt

    d_dt = min(direct() for _ in range(3))
    p_dt = min(prefetched() for _ in range(3))
    rec = {"direct_rec_s": round(n_records / d_dt, 1),
           "prefetched_rec_s": round(n_records / p_dt, 1),
           "overlap_gain": round(d_dt / p_dt, 2)}
    log(f"prefetcher: {rec['prefetched_rec_s']} rec/s vs direct "
        f"{rec['direct_rec_s']} rec/s (gain {rec['overlap_gain']}x)")
    return rec


def bench_dataloader(n_jpegs: int, workers: int, tmp: str):
    """DataLoader samples/s with the standard train transform over real
    JPEG files (PIL decode on the worker side)."""
    from mxnet_tpu import image as mximage
    from mxnet_tpu import np as mxnp
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.vision import ImageListDataset, transforms

    img_dir = os.path.join(tmp, "imgs")
    os.makedirs(img_dir, exist_ok=True)
    rs = onp.random.RandomState(0)
    items = []
    for i in range(n_jpegs):
        arr = rs.randint(0, 255, (256, 256, 3), dtype=onp.uint8)
        fname = os.path.join(img_dir, f"{i}.jpg")
        mximage.imsave(fname, mxnp.array(arr))
        items.append((fname, i % 10))
    ds = ImageListDataset(img_dir, [(lab, os.path.basename(f))
                                    for f, lab in items])
    tf = transforms.Compose([
        transforms.RandomResizedCrop(224),
        transforms.RandomFlipLeftRight(),
        transforms.ToTensor(),
        transforms.Normalize(0.5, 0.25),
    ])
    ds_t = ds.transform_first(tf)

    out = {}
    for nw in (0, workers):
        loader = DataLoader(ds_t, batch_size=32, shuffle=True,
                            num_workers=nw)
        # one warm epoch (worker startup, caches), one timed
        for _ in loader:
            pass
        t0 = time.perf_counter()
        n = 0
        for x, y in loader:
            n += x.shape[0]
        dt = time.perf_counter() - t0
        key = "loader0_sps" if nw == 0 else f"loader{nw}_sps"
        out[key] = round(n / dt, 1)
        log(f"dataloader workers={nw}: {out[key]} samples/s")
    if workers:
        out["worker_speedup"] = round(
            out[f"loader{workers}_sps"] / out["loader0_sps"], 2)
    out["jpegs"] = n_jpegs
    out["transform"] = "RandomResizedCrop(224)+Flip+ToTensor+Normalize"
    return out


def _make_jpeg_rec(tmp: str, name: str, n_jpegs: int, src_hw=(480, 640),
                   quality: int = 85, seed: int = 2,
                   collect_payloads: bool = False):
    """One synthetic photo-like JPEG RecordIO for every bench stage;
    ``collect_payloads`` also returns the raw JPEG payloads for stages
    that decode bytes directly."""
    from mxnet_tpu import recordio

    rng = onp.random.RandomState(seed)
    path = os.path.join(tmp, name)
    rec = recordio.MXRecordIO(path, "w")
    payloads = [] if collect_payloads else None
    for i in range(n_jpegs):
        im = rng.randint(0, 255, src_hw + (3,)).astype(onp.uint8)
        packed = recordio.pack_img(recordio.IRHeader(0, float(i), i, 0),
                                   im, quality=quality)
        if payloads is not None:
            payloads.append(recordio.unpack(packed)[1])
        rec.write(packed)
    rec.close()
    return (path, payloads) if collect_payloads else path


def bench_native_decode(n_jpegs: int, tmp: str, hw: int = 224):
    """The chip-feeding number (VERDICT r4 item #4): JPEG bytes ->
    (224,224,3) uint8 via the C++ libjpeg pipeline (decode-time IDCT
    downscale + bilinear) vs the PIL per-image path. Single-thread is
    the honest comparison on this 1-CPU host; the n_threads=4 row shows
    pool behavior (expect ~1x here, >3x on real multi-core hosts)."""
    from mxnet_tpu.image import _to_np, imdecode, imresize
    from mxnet_tpu.io import decode_jpeg_batch, native_available

    if not native_available():
        return {"skipped": "native pipeline unavailable"}
    # realistic source: 480x640 photos JPEG-compressed at q85
    _, payloads = _make_jpeg_rec(tmp, "decode.rec", n_jpegs, seed=0,
                                 collect_payloads=True)
    total_mb = sum(len(p) for p in payloads) / 1e6

    t0 = time.perf_counter()
    for p in payloads:
        _to_np(imresize(imdecode(p), hw, hw))
    dt_pil = time.perf_counter() - t0

    t0 = time.perf_counter()
    decode_jpeg_batch(payloads, hw, hw, n_threads=1)
    dt_nat1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    decode_jpeg_batch(payloads, hw, hw, n_threads=4)
    dt_nat4 = time.perf_counter() - t0

    out = {
        "jpegs": n_jpegs,
        "source": "480x640 q85",
        "target": f"{hw}x{hw}",
        "pil_img_s": round(n_jpegs / dt_pil, 1),
        "native_1thread_img_s": round(n_jpegs / dt_nat1, 1),
        "native_4thread_img_s": round(n_jpegs / dt_nat4, 1),
        "native_1thread_mb_s": round(total_mb / dt_nat1, 1),
        "native_vs_pil_1thread": round(dt_pil / dt_nat1, 2),
        "native_pool_speedup": round(dt_nat1 / dt_nat4, 2),
    }
    log(f"decode: PIL {out['pil_img_s']} img/s, native(1t) "
        f"{out['native_1thread_img_s']} img/s "
        f"({out['native_vs_pil_1thread']}x), native(4t) "
        f"{out['native_4thread_img_s']} img/s")
    return out


def bench_native_pipeline(n_jpegs: int, tmp: str, hw: int = 224):
    """End-to-end: RecordIO bytes -> batched uint8 through the C++
    read-ahead + decode-pool pipeline (NativeImagePipeline)."""
    from mxnet_tpu.io import NativeImagePipeline, native_available

    if not native_available():
        return {"skipped": "native pipeline unavailable"}
    path = _make_jpeg_rec(tmp, "pipe.rec", n_jpegs, seed=1)
    pipe = NativeImagePipeline(path, (3, hw, hw), batch_size=32,
                               n_threads=2)
    n = sum(d.shape[0] for d, _ in pipe)  # warm (page cache, pool)
    pipe.reset()
    t0 = time.perf_counter()
    n = sum(d.shape[0] for d, _ in pipe)
    dt = time.perf_counter() - t0
    pipe.close()
    # augmented decode (rand crop + mirror in the C++ workers): the
    # augmentation is folded into the window-resize mapping, so the
    # honest claim "augmented decode costs about the same as plain
    # decode" gets a measured number (crop decodes at higher IDCT
    # resolution — min_area^-0.5 — so a modest slowdown is expected)
    pipe = NativeImagePipeline(path, (3, hw, hw), batch_size=32,
                               n_threads=2, rand_crop=True,
                               rand_mirror=True, seed=1)
    n_aug = sum(d.shape[0] for d, _ in pipe)
    pipe.reset()
    t0 = time.perf_counter()
    n_aug = sum(d.shape[0] for d, _ in pipe)
    dt_aug = time.perf_counter() - t0
    pipe.close()
    out = {"img_s": round(n / dt, 1), "batch": 32,
           "augmented_img_s": round(n_aug / dt_aug, 1),
           "augment_relative_cost": round(dt_aug / dt, 2),
           "bytes_per_img": "~55KB jpeg",
           "chip_feed_estimate": (
               "per-host img/s scales ~linearly with decode cores; a "
               "224px ResNet step at 7.5k img/s needs ~26 of these "
               "single-core pipelines — a v5e host has 112 vCPU")}
    log(f"native pipeline end-to-end: {out['img_s']} img/s (1 core)")
    return out


def bench_sharded(n_jpegs: int, tmp: str, hw: int = 224,
                  worker_counts=(1, 2, 4)):
    """The tentpole stage: multi-process sharded decode through
    shared-memory ring slabs vs one process, same data. Per-worker
    decode is CPU-bound, so the scaling ceiling is min(workers, cpus) —
    the cpus field in the artifact is part of the number."""
    from mxnet_tpu.io import ShardedImagePipeline, native_available

    if not native_available():
        return {"skipped": "native pipeline unavailable"}
    path = _make_jpeg_rec(tmp, "sharded.rec", n_jpegs)
    out = {"jpegs": n_jpegs, "source": "480x640 q85",
           "target": f"{hw}x{hw}", "batch": 32}
    for nw in worker_counts:
        pipe = ShardedImagePipeline(path, (3, hw, hw), 32, num_workers=nw,
                                    n_threads=1, ring_depth=3)
        n = sum(d.shape[0] for d, _ in pipe)  # warm: spawn + page cache
        pipe.reset()
        t0 = time.perf_counter()
        n = sum(d.shape[0] for d, _ in pipe)
        dt = time.perf_counter() - t0
        pipe.close()
        assert n == n_jpegs
        out[f"workers{nw}_img_s"] = round(n / dt, 1)
        log(f"sharded decode {nw}w: {out[f'workers{nw}_img_s']} img/s")
    base = out.get(f"workers{worker_counts[0]}_img_s")
    peak_w = worker_counts[-1]
    if base:
        out["speedup_at_max_workers"] = round(
            out[f"workers{peak_w}_img_s"] / base, 2)
    return out


def bench_epoch_cache(n_jpegs: int, tmp: str, hw: int = 168):
    """Decoded-batch epoch cache: live decode vs the banking epoch
    (decode + append-write) vs cached streaming (memmap slices, no
    libjpeg). The canvas is the padded on-device-augment size, not the
    train crop — the config docs/data.md recommends."""
    from mxnet_tpu.io import (CachedImagePipeline, NativeImagePipeline,
                              native_available)

    if not native_available():
        return {"skipped": "native pipeline unavailable"}
    path = _make_jpeg_rec(tmp, "cache.rec", n_jpegs)
    shape = (3, hw, hw)

    def epoch(pipe):
        """Consume EVERY byte (cached batches are lazy memmap views — a
        shape-only walk would 'stream' at infinity img/s)."""
        n, sink = 0, 0
        for d, _ in pipe:
            n += d.shape[0]
            sink += int(d.sum())
        return n, sink

    live = NativeImagePipeline(path, shape, 32, n_threads=1)
    n, _ = epoch(live)  # warm
    live.reset()
    t0 = time.perf_counter()
    n, _ = epoch(live)
    dt_live = time.perf_counter() - t0
    live.close()

    cdir = os.path.join(tmp, "iocache")
    cp = CachedImagePipeline(
        lambda: NativeImagePipeline(path, shape, 32, n_threads=1),
        cdir, path, shape, 32)
    t0 = time.perf_counter()
    n_bank, _ = epoch(cp)  # epoch 1: decode + bank
    dt_bank = time.perf_counter() - t0
    cp.reset()
    n_c, _ = epoch(cp)  # warm the page cache
    cp.reset()
    t0 = time.perf_counter()
    n_c, _ = epoch(cp)
    dt_cached = time.perf_counter() - t0
    cp.close()
    assert n == n_bank == n_c == n_jpegs
    row_mb = n_jpegs * hw * hw * 3 / 1e6
    out = {
        "jpegs": n_jpegs, "canvas": f"{hw}x{hw}",
        "live_img_s": round(n / dt_live, 1),
        "bank_epoch_img_s": round(n / dt_bank, 1),
        "cached_img_s": round(n / dt_cached, 1),
        "cached_mb_s": round(row_mb / dt_cached, 1),
        "cached_vs_live": round(dt_live / dt_cached, 2),
        "bank_overhead_vs_live": round(dt_bank / dt_live, 2),
    }
    log(f"epoch cache: live {out['live_img_s']} img/s, bank "
        f"{out['bank_epoch_img_s']} img/s, cached {out['cached_img_s']} "
        f"img/s ({out['cached_vs_live']}x live)")
    return out


def bench_device_prefetch(n_jpegs: int, tmp: str, hw: int = 168,
                          depth: int = 3):
    """Depth-K device staging with the new attribution counters: a
    synthetic 5 ms 'train step' consumes batches while the feeder
    stages them; starved_s says how much of the epoch the step spent
    waiting on input — THE number that closes the loop on
    results_train_io_tpu.json's input_overhead_pct."""
    from mxnet_tpu.io import (DevicePrefetch, NativeImagePipeline,
                              native_available)

    if not native_available():
        return {"skipped": "native pipeline unavailable"}
    path = _make_jpeg_rec(tmp, "prefetch.rec", n_jpegs)
    pipe = NativeImagePipeline(path, (3, hw, hw), 32, n_threads=1,
                               pad_last=True)
    dp = DevicePrefetch(pipe, depth=depth)
    step_s = 0.005
    t0 = time.perf_counter()
    n = 0
    for data, label, valid in dp:
        time.sleep(step_s)  # the jitted step's slot
        n += int(valid)
    dt = time.perf_counter() - t0
    st = dp.stats
    dp.close()
    pipe.close()
    out = {
        "jpegs": n_jpegs, "depth": depth, "step_ms": step_s * 1e3,
        "img_s": round(n / dt, 1),
        "batches": st["batches"],
        "bytes_staged": st["bytes_staged"],
        "starved_s": st["starved_s"],
        "starved_pct_of_wall": round(100 * st["starved_s"] / dt, 1),
        "queue_depth_at_end": st["queue_depth"],
    }
    log(f"device prefetch depth={depth}: {out['img_s']} img/s, starved "
        f"{out['starved_s']}s ({out['starved_pct_of_wall']}% of wall)")
    return out


def main():
    # host-side benchmark: never touch the accelerator backend
    # (ToTensor/np paths would initialize it)
    import jax

    jax.config.update("jax_platforms", "cpu")

    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=2000)
    ap.add_argument("--payload", type=int, default=64 * 1024)
    ap.add_argument("--jpegs", type=int, default=600)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--output", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="smoke mode: tiny synthetic data, every stage "
                    "exercised, seconds not minutes (the tier-1 gate)")
    args = ap.parse_args()

    if args.quick:
        args.records, args.payload, args.jpegs = 64, 8192, 48
        args.workers = 2

    import platform

    with tempfile.TemporaryDirectory() as tmp:
        rec_io, path = bench_recordio(args.records, args.payload, tmp)
        rec_pf = bench_prefetcher(path, args.records)
        rec_dl = bench_dataloader(args.jpegs, args.workers, tmp)
        rec_dec = bench_native_decode(min(args.jpegs, 200), tmp)
        rec_pipe = bench_native_pipeline(min(args.jpegs, 200), tmp)
        if args.quick:
            rec_shard = bench_sharded(args.jpegs, tmp, hw=64,
                                      worker_counts=(1, 2))
            rec_cache = bench_epoch_cache(args.jpegs, tmp, hw=64)
            rec_dp = bench_device_prefetch(args.jpegs, tmp, hw=64)
        else:
            rec_shard = bench_sharded(min(args.jpegs, 400), tmp)
            rec_cache = bench_epoch_cache(min(args.jpegs, 400), tmp)
            rec_dp = bench_device_prefetch(min(args.jpegs, 400), tmp)
    try:
        cpus = len(os.sched_getaffinity(0))
    except AttributeError:
        cpus = os.cpu_count()
    out = {"recordio": rec_io, "prefetcher": rec_pf, "dataloader": rec_dl,
           "native_decode": rec_dec, "native_pipeline": rec_pipe,
           "sharded_pipeline": rec_shard, "epoch_cache": rec_cache,
           "device_prefetch": rec_dp,
           "host": platform.processor() or platform.machine(),
           "cpus": cpus,
           "quick": bool(args.quick),
           "note": ("thread/process overlap gains are meaningful only "
                    "when cpus > 1; sharded decode is CPU-bound so its "
                    "scaling ceiling is min(workers, cpus) — the "
                    "speedup_at_max_workers row must be read against "
                    "the cpus field. The epoch-cache row is CPU-count "
                    "independent: it replaces decode with memmap "
                    "streaming.")}
    text = json.dumps(out, indent=2)
    print(text)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()
