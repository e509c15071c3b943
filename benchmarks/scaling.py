"""Distributed-engine strong scaling: the same counting workload on host
meshes of 1..8 CPU devices.  The subprocess is pinned to the CPU — it times
forced host devices, never a chip, which this process may hold.  Derived
column: speedup vs 1 device and exactness check."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

from .common import Row

SCRIPT = r"""
import os, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
from repro.mining import ItemVocab, class_weights, encode_bitmap, encode_targets
from repro.mining.distributed import distributed_counts

rng = np.random.default_rng(0)
N, M, K = 60000, 48, 512
mat = rng.random((N, M)) < 0.2
tx = [np.flatnonzero(r).tolist() for r in mat]
y = rng.integers(0, 2, N)
vocab = ItemVocab(tuple(range(M)))
bits = encode_bitmap(tx, vocab)
w = class_weights(y, 2)
tgts = []
for _ in range(K):
    tgts.append(sorted(rng.choice(M, size=rng.integers(1, 4), replace=False).tolist()))
masks = encode_targets(tgts, vocab)

out = {}
ref = None
for d in (1, 2, 4, 8):
    mesh = jax.make_mesh((d,), ("data",), devices=jax.devices()[:d])
    # warm
    distributed_counts(bits, masks, w, mesh, model_axis=None)
    t0 = time.perf_counter()
    got = distributed_counts(bits, masks, w, mesh, model_axis=None)
    dt = time.perf_counter() - t0
    if ref is None:
        ref = got
    assert (got == ref).all()
    out[d] = dt * 1e6
print(json.dumps(out))
"""


def run() -> List[Row]:
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"      # forced host devices, not the chip
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-1500:])
    data = json.loads(proc.stdout.strip().splitlines()[-1])
    base = data["1"]
    rows: List[Row] = []
    for d, us in data.items():
        rows.append((f"scaling[cpu_host_devices={d}]", us,
                     f"speedup_vs_1dev={base / us:.2f}x"))
    return rows
