#!/usr/bin/env python
"""Data-pipeline throughput benchmark.

Reference baseline: >1,000 images/sec decoded at 4 decode threads
(docs/static_site/src/pages/api/faq/perf.md:277-280).  This drives the
native C++ pipeline (src/native/dataloader.cc: pread record access,
libjpeg decode workers, double-buffered batch staging) through the same
ImageRecordIter users run.

Usage::

    python benchmark/data_bench.py [--images 4096] [--threads 4]
                                   [--size 224] [--out results.json]

Prints ONE json line {"metric", "value", "unit", "vs_baseline"}.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

BASELINE_IMGS_PER_SEC = 1000.0  # perf.md:277-280, 4 decode threads


def make_recordio(path, n_images, size):
    """Synthesize a JPEG RecordIO file (test_native.py recipe)."""
    from mxnet_tpu import native, recordio

    rs = np.random.RandomState(0)
    writer = recordio.MXRecordIO(path, "w")
    # a few distinct images re-encoded (decode cost dominates; content
    # variety keeps the JPEG huffman tables honest)
    blobs = []
    for i in range(16):
        img = (rs.rand(size, size, 3) * 255).astype(np.uint8)
        blobs.append(native.encode_jpeg(img, quality=90))
    for i in range(n_images):
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        writer.write(recordio.pack(header, blobs[i % len(blobs)]))
    writer.close()


def train_from_loader(rec, args):
    """End-to-end loader-fed training (VERDICT r3 #5): ResNet-50 bf16
    where every batch rides RecordIO -> decode workers -> host batch ->
    device transfer -> fused train step.  The honest number to put next
    to the device-staged bench row."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import io as mxio, nd, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    net = vision.resnet50_v1()
    net.initialize()
    trainer = parallel.FusedTrainer(
        net, loss="softmax_ce", optimizer="sgd",
        optimizer_params={"learning_rate": 0.05, "momentum": 0.9},
        dtype="bfloat16")
    it = mxio.ImageRecordIter(
        path_imgrec=rec, data_shape=(3, args.size, args.size),
        batch_size=args.batch, preprocess_threads=args.threads,
        rand_mirror=True)
    # one warmup batch compiles the step
    first = next(iter(it))
    loss = trainer.step(first.data[0].astype("float32") / 255.0,
                        first.label[0].astype("int32"))
    float(loss.asnumpy())
    it.reset()
    t0 = time.perf_counter()
    n = 0
    for batch in it:
        x = batch.data[0].astype("float32") / 255.0
        y = batch.label[0].astype("int32")
        loss = trainer.step(x, y)
        n += x.shape[0]
    float(loss.asnumpy())   # hard sync
    dt = time.perf_counter() - t0
    row = {"metric": "resnet50_train_bf16_loader_fed_imgs_per_sec",
           "value": round(n / dt, 2), "unit": "img/s",
           "vs_baseline": None,
           "extra": {"images": n, "seconds": round(dt, 3),
                     "threads": args.threads, "batch": args.batch,
                     "backend": jax.default_backend()}}
    try:
        # ISSUE 15: loader-fed vs pre-staged CAPTURED steps through
        # the mx.data prefetch ring — the committed H3 number
        row["captured_ring"] = captured_ring_row(rec, args)
    except Exception as exc:  # noqa: BLE001 — fail-soft like mfu rows
        row["captured_ring"] = {"error": repr(exc)}
    return row


def _stream_decode(raw):
    """StreamLoader decode for the bench RecordIO: JPEG -> float32
    NCHW in [0,1] (module-level so thread workers share it)."""
    from mxnet_tpu.data import default_decode

    img, label = default_decode(raw)
    x = np.ascontiguousarray(img.transpose(2, 0, 1)).astype(
        np.float32) / 255.0
    return x, label.astype(np.float32)


def captured_ring_row(rec, args, steps=8):
    """Loader-fed vs pre-staged CAPTURED steps (ISSUE 15): the same
    ResNet-50 whole-step program (mx.step) timed once over batches the
    mx.data prefetch ring streams from RecordIO and once over batches
    pre-staged on device — the committed H3 host-gap number.  The ring
    (depth >= 2) should put the loader-fed column within 5% of
    pre-staged; the gap IS the host share the ring failed to hide."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import data as mxdata, gluon, telemetry
    from mxnet_tpu.gluon.model_zoo import vision

    def build():
        mx.random.seed(0)
        net = vision.resnet50_v1()
        net.initialize()
        net.hybridize()
        trainer = gluon.Trainer(
            net.collect_params(), "sgd",
            {"learning_rate": 0.05, "momentum": 0.9})
        return net, trainer.capture(
            net, gluon.loss.SoftmaxCrossEntropyLoss())

    batch = args.batch

    def loader():
        return mxdata.StreamLoader(
            rec, batch_size=batch, seed=1, decode_fn=_stream_decode,
            num_workers=args.threads, prefetch=None)  # MXNET_DATA_PREFETCH depth

    # pre-staged: batches already device-resident before the clock
    _net, prog = build()
    ldr = loader()
    staged = []
    for x, y in iter(ldr):
        staged.append((x, y))
        if len(staged) >= steps + 1:
            break
    ldr.close()
    prog(*staged[0])
    t0 = time.perf_counter()
    for x, y in staged[1:]:
        loss = prog(x, y)
    float(loss.asnumpy().sum())
    pre_s = (time.perf_counter() - t0) / steps

    # loader-fed: the ring streams RecordIO->decode->device in flight
    _net2, prog2 = build()
    ldr2 = loader()
    it = iter(ldr2)
    x, y = next(it)
    prog2(x, y)
    telemetry.reset()
    n = 0
    t0 = time.perf_counter()
    for x, y in it:
        loss = prog2(x, y)
        n += 1
        if n >= steps:
            break
    float(loss.asnumpy().sum())
    fed_s = (time.perf_counter() - t0) / max(1, n)
    qs = telemetry.histogram_quantiles("dataloader_batch_wait_seconds")
    stats = ldr2.stats()
    ldr2.close()
    return {
        "prestaged_ms_per_step": round(pre_s * 1e3, 3),
        "loader_fed_ms_per_step": round(fed_s * 1e3, 3),
        "gap_pct": round((fed_s - pre_s) / pre_s * 100.0, 2),
        "batch_wait_p99_ms": round(qs.get(0.99, 0.0) * 1e3, 3),
        "ring_depth": stats["ring_depth"],
        "ring_stalls": stats["ring_stalls"],
        "workers": stats["workers"],
        "steps": n,
        "backend": jax.default_backend(),
    }


def loader_scaling(rec, args):
    """Decode throughput at 1..max threads (reference multi-threaded
    pipeline: iter_image_recordio_2.cc:154 decode thread pool)."""
    from mxnet_tpu import io as mxio

    rows = {}
    for threads in (1, 2, 4, 8):
        if threads > (os.cpu_count() or 1):
            break
        it = mxio.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, args.size, args.size),
            batch_size=args.batch, preprocess_threads=threads)
        n = 0
        for batch in it:    # warm page cache + JIT paths
            n += batch.data[0].shape[0]
        it.reset()
        t0 = time.perf_counter()
        n = 0
        for batch in it:
            n += batch.data[0].shape[0]
        dt = time.perf_counter() - t0
        rows[str(threads)] = round(n / dt, 1)
    return {"metric": "image_decode_scaling_imgs_per_sec",
            "value": rows.get("4") or max(rows.values()),
            "unit": "img/s", "vs_baseline": None,
            "extra": {"per_threads": rows,
                      "cpu_cores": os.cpu_count()}}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--images", type=int, default=4096)
    parser.add_argument("--threads", type=int, default=4)
    parser.add_argument("--size", type=int, default=224)
    parser.add_argument("--batch", type=int, default=128)
    parser.add_argument("--out", default=None)
    parser.add_argument("--train", action="store_true",
                        help="loader-fed ResNet-50 bf16 training row")
    parser.add_argument("--scaling", action="store_true",
                        help="decode throughput at 1/2/4/8 workers")
    args = parser.parse_args(argv)

    from mxnet_tpu.compile import jax_cache_dir

    jax_cache_dir()
    from mxnet_tpu import io as mxio

    if args.train or args.scaling:
        with tempfile.TemporaryDirectory() as td:
            rec = os.path.join(td, "bench.rec")
            make_recordio(rec, args.images, args.size)
            row = (train_from_loader if args.train
                   else loader_scaling)(rec, args)
        print(json.dumps(row))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(row, f, indent=2)
        return 0

    with tempfile.TemporaryDirectory() as td:
        rec = os.path.join(td, "bench.rec")
        make_recordio(rec, args.images, args.size)

        it = mxio.ImageRecordIter(
            path_imgrec=rec, data_shape=(3, args.size, args.size),
            batch_size=args.batch, preprocess_threads=args.threads,
            rand_mirror=True)
        # warmup epoch (touches every record; OS page cache warm)
        n = 0
        for batch in it:
            n += batch.data[0].shape[0]
        it.reset()
        t0 = time.perf_counter()
        n = 0
        for batch in it:
            n += batch.data[0].shape[0]
        dt = time.perf_counter() - t0

    ips = n / dt
    row = {"metric": "image_decode_pipeline_imgs_per_sec_%dthreads"
                     % args.threads,
           "value": round(ips, 1), "unit": "img/s",
           "vs_baseline": round(ips / BASELINE_IMGS_PER_SEC, 3),
           "extra": {"images": n, "seconds": round(dt, 3),
                     "size": args.size, "batch": args.batch,
                     # the reference's >1000 img/s ran 4 decode threads on
                     # a multi-core CPU; normalize per available core
                     "cpu_cores": os.cpu_count(),
                     "imgs_per_sec_per_core": round(
                         ips / max(os.cpu_count(), 1), 1)}}
    print(json.dumps(row))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(row, f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
