"""End-to-end input-pipeline -> training benchmark.

The north-star metric (BASELINE.json) is ImageNet images/sec — which the
reference measured with its C++ decode/augment pipeline FEEDING the
trainer (iter_image_recordio_2.cc:50), not synthetic-fed.  This tool
measures that composition as ONE loop:

    ImageRecordIter(preprocess_threads=N)  ->  DevicePrefetchIter
        ->  Module fused train step

and reports, as one JSON line:
  e2e_img_s          images/sec of the composed loop
  input_img_s        the pipeline alone (decode+augment+batch, no train)
  device_img_s       the train step alone (synthetic-fed, device-bound)
  accel_idle_frac    1 - e2e/device: fraction of chip capacity the input
                     side leaves idle on THIS host
  overlap_efficiency e2e / min(input, device): 1.0 = the prefetch
                     overlap hides the slower side completely
  bottleneck         which side bounds the composed number

A synthetic .rec of real JPEGs is packed on the fly so the decode cost
is genuine.  Run on the bench host for the number of record; CI hosts
report their own (slower) input side — say so when quoting.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def make_rec(path, n, hw, rng):
    """Pack n random JPEGs (real cv2 encode) into a .rec + .idx pair."""
    import cv2
    from mxnet_tpu import recordio
    idx_path = path + ".idx"
    rec = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(n):
        img = rng.randint(0, 256, (hw, hw, 3), dtype=np.uint8)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        ok, buf = cv2.imencode(".jpg", img)
        assert ok
        rec.write_idx(i, recordio.pack(header, buf.tobytes()))
    rec.close()
    return path, idx_path


def build_module(mx, ctx, num_layers, image_shape, batch):
    from mxnet_tpu.models import resnet
    sym = resnet.get_symbol(num_classes=1000, num_layers=num_layers,
                            image_shape=",".join(map(str, image_shape)))
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[("data", (batch,) + image_shape)],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9,
                                         "rescale_grad": 1.0 / batch})
    return mod


def time_loop(fn, n_batches, warmup=2):
    for _ in range(warmup):
        fn(warm=True)
    t0 = time.perf_counter()
    images = 0
    for _ in range(n_batches):
        images += fn(warm=False)
    return images / (time.perf_counter() - t0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=512,
                    help="images packed into the synthetic .rec")
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("--num-layers", type=int, default=50)
    ap.add_argument("--preprocess-threads", type=int,
                    default=os.cpu_count() or 1)
    args = ap.parse_args()

    import jax
    import mxnet_tpu as mx

    on_chip = mx.on_tpu()
    ctx = mx.tpu() if on_chip else mx.cpu()
    shape = (3, args.hw, args.hw)
    rng = np.random.RandomState(0)

    tmpd = tempfile.mkdtemp(prefix="e2e_bench_")
    rec_path, idx_path = make_rec(os.path.join(tmpd, "data.rec"),
                                  args.images, args.hw, rng)

    def make_iter():
        it = mx.io.ImageRecordIter(
            path_imgrec=rec_path, path_imgidx=idx_path,
            data_shape=shape, batch_size=args.batch_size,
            rand_mirror=True, mean_r=123.68, mean_g=116.78,
            mean_b=103.94, preprocess_threads=args.preprocess_threads)
        return mx.io.DevicePrefetchIter(it, ctx=ctx)

    mod = build_module(mx, ctx, args.num_layers, shape, args.batch_size)

    # 1. input side alone (decode+augment+batch+upload, no train)
    it = make_iter()

    def input_only(warm):
        try:
            b = it.next()
        except StopIteration:
            it.reset()
            b = it.next()
        b.data[0].wait_to_read()
        return args.batch_size

    input_img_s = time_loop(input_only, args.batches)

    # 2. device side alone: same fused step re-fed one resident batch
    it.reset()
    resident = it.next()

    def device_only(warm):
        mod.forward_backward(resident)
        mod.update()
        # drain async dispatch so the rate is the real step rate
        mod.get_outputs()[0].wait_to_read()
        return args.batch_size

    device_img_s = time_loop(device_only, args.batches)

    # 3. the composed loop — the honest number
    it.reset()

    def e2e(warm):
        try:
            b = it.next()
        except StopIteration:
            it.reset()
            b = it.next()
        mod.forward_backward(b)
        mod.update()
        mod.get_outputs()[0].wait_to_read()
        return args.batch_size

    e2e_img_s = time_loop(e2e, args.batches)

    slower = min(input_img_s, device_img_s)
    print(json.dumps({
        "metric": "e2e_pipeline_train",
        "value": round(e2e_img_s, 2),
        "unit": "images/sec",
        "input_img_s": round(input_img_s, 2),
        "device_img_s": round(device_img_s, 2),
        "accel_idle_frac": round(max(0.0, 1 - e2e_img_s / device_img_s), 3),
        "overlap_efficiency": round(e2e_img_s / slower, 3) if slower else None,
        "bottleneck": "input_pipeline" if input_img_s < device_img_s
        else "device_compute",
        "preprocess_threads": args.preprocess_threads,
        "host_cpus": os.cpu_count(),
        "batch_size": args.batch_size,
        "model": "resnet-%d_%dx%d" % (args.num_layers, args.hw, args.hw),
        "device_kind": jax.devices()[0].device_kind,
    }))


if __name__ == "__main__":
    main()
