"""k-means through the LA flavor — the paper's Fig. 2 (right) workload.

Shows the two CVM mechanisms the paper credits for matching hand-written
C++ k-means:
  * plan analysis / fusion: CDist2→ArgMinRow→SegSum/SegCount collapses into
    the fused la.KMeansStep ("run-based aggregation"),
  * the parallelization rewrite: points Split, centroids Broadcast,
    partials CombineChunks.

Run: PYTHONPATH=src python examples/kmeans.py
"""

import time

import numpy as np

from repro.backends.local import LocalBackend
from repro.core import Builder, verify
from repro.core.passes import FuseKMeansStep, Parallelize
from repro.core.types import F32, Tensor
from repro.launch.cache import enable_compile_cache

enable_compile_cache()

n, d, k, iters = 1 << 15, 8, 16, 5
rng = np.random.default_rng(0)
true_centers = rng.normal(0, 5, (k, d)).astype(np.float32)
X = (true_centers[rng.integers(0, k, n)] + rng.normal(0, 1, (n, d))).astype(np.float32)
C0 = X[rng.choice(n, k, replace=False)]

# -- build the UNFUSED program (what a frontend would emit) --------------------
b = Builder("kmeans_iter")
xr = b.input("X", Tensor(F32, (n, d)))
cr = b.input("C", Tensor(F32, (k, d)))
dist = b.emit1("la.CDist2", [xr, cr])
lab = b.emit1("la.ArgMinRow", [dist])
sums = b.emit1("la.SegSum", [xr, lab], {"k": k})
counts = b.emit1("la.SegCount", [lab], {"k": k})
program = b.finish(sums, counts)
print("== frontend program ==")
print(program.render())

# -- fusion + parallelization rewrites -----------------------------------------
program = FuseKMeansStep().apply(program)
program = Parallelize(n=8, targets={xr.name}).apply(program)
verify(program)
print("\n== after FuseKMeansStep + Parallelize(8) ==")
print(program.render())

compiled = LocalBackend().compile(program)


def step(x, c):
    sums, counts = compiled({}, x, c)
    counts = np.maximum(np.asarray(counts), 1e-9)
    return np.asarray(sums) / counts[:, None]


# -- run ------------------------------------------------------------------------
C = C0.copy()
step(X, C)  # warm-up / compile
t0 = time.time()
for it in range(iters):
    C = step(X, C)
cvm_t = (time.time() - t0) / iters

# numpy "sklearn-style" baseline
def np_step(x, c):
    d2 = ((x[:, None, :] - c[None, :, :]) ** 2).sum(-1)
    labf = np.argmin(d2, axis=1)
    sums = np.zeros((k, d)); np.add.at(sums, labf, x)
    cnt = np.maximum(np.bincount(labf, minlength=k), 1)
    return sums / cnt[:, None]

Cn = C0.copy()
t0 = time.time()
for it in range(iters):
    Cn = np_step(X, Cn)
np_t = (time.time() - t0) / iters

err = np.abs(np.sort(C, axis=0) - np.sort(Cn, axis=0)).max()
print(f"\nCVM-compiled k-means: {cvm_t*1e3:.1f} ms/iter; "
      f"numpy baseline: {np_t*1e3:.1f} ms/iter; speedup ×{np_t/cvm_t:.1f}")
print(f"centroid agreement (sorted) max|Δ| = {err:.2e}")
assert err < 1e-2
