"""Side-effect-free helpers shared by the benchmark scripts.

Kept free of jax/config imports on purpose: a caller may import
pack_rec mid-run on a live TPU backend, so this module must not touch
backend or platform configuration at import time (the input_pipeline
SCRIPT forces the CPU platform for itself; that belongs in its
__main__, not here).
"""
import os

import numpy as np


def pack_rec(tmpdir, n_images, size=224):
    """Write a synthetic ImageNet-shaped .rec/.idx pair and return the
    paths. JPEG content is smooth-gradient + noise (realistic entropy:
    pure noise decodes slower and compresses terribly)."""
    from mxnet_tpu import recordio

    rng = np.random.RandomState(0)
    rec = os.path.join(tmpdir, "bench.rec")
    idx = os.path.join(tmpdir, "bench.idx")
    writer = recordio.MXIndexedRecordIO(idx, rec, "w")
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    for i in range(n_images):
        base = (127 + 60 * np.sin(xx / (7 + i % 13))
                + 40 * np.cos(yy / (11 + i % 7)))
        img = np.clip(base[..., None] + rng.randn(size, size, 3) * 20,
                      0, 255).astype(np.uint8)
        writer.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img))
    writer.close()
    return rec, idx
