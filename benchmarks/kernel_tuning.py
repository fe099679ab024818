"""Seg-scan kernel tuning microbenchmarks -> BENCH_kernel.json.

Measures the DES scan hot path end-to-end (``simulate_completion_scan``)
per execution path — lax baseline, v2 fused kernel per candidate chunk,
v2 at the roofline-autotuned chunk — plus the legacy v1 matmul kernel in
isolation, and records the autotuner's analytic ranking next to the
measured times (maxtext-microbenchmark style: cached jitted callables,
best-of-repeats walls).

Off-TPU every kernel number is the INTERPRET/EMULATION fallback, never a
compiled accelerator kernel; the payload carries ``kernel_path`` so the
provenance is explicit (satellite of the one-time
``KernelInterpretFallbackWarning``).  The v1 kernel runs under the actual
Pallas interpreter, which pays per-grid-step Python overhead, so it is
measured at a smaller size and labelled with its own ``n_cloudlets``.
"""
import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import emit, smoke, timed
from repro.core.compat import kernel_path
from repro.core.des_scan import simulate_completion_scan_jit
from repro.roofline import autotune

BENCH_JSON = "BENCH_kernel.json"


def _scan_inputs(C, V, seed=0):
    rng = np.random.default_rng(seed)
    assign = jnp.asarray(rng.integers(0, V, C).astype(np.int32))
    mi = jnp.asarray(rng.uniform(1e3, 5e4, C).astype(np.float32))
    mips = jnp.asarray(rng.uniform(500.0, 2000.0, V).astype(np.float32))
    valid = jnp.asarray(rng.uniform(size=C) < 0.97)
    return assign, mi, mips, valid


def _scan_entry(core, C, t, chunk=None, **extra):
    e = {"core": core, "n_cloudlets": int(C), "scan_s": float(t), **extra}
    if chunk is not None:
        e["chunk"] = int(chunk)
    emit(f"kernel/{core.split('/', 1)[1]}_C{C}"
         + (f"_chunk{chunk}" if chunk is not None else ""), t * 1e6,
         extra.get("derived", ""))
    return e


def main():
    sizes = [4096] if smoke() else [65536, 1 << 20]
    chunks = (64, 128) if smoke() else (64, 128, 256)
    v1_size = 1024 if smoke() else 16384
    path = kernel_path(True)
    entries = []

    for C in sizes:
        V = max(C // 16, 4)
        args = _scan_inputs(C, V)

        t_lax, (f_lax, _) = timed(
            lambda: simulate_completion_scan_jit(*args), repeats=3)
        entries.append(_scan_entry("kernel/lax", C, t_lax))

        for chunk in chunks:
            t_k, (f_k, _) = timed(
                lambda c=chunk: simulate_completion_scan_jit(
                    *args, use_kernel=True, kernel_chunk=c), repeats=3)
            assert np.array_equal(np.asarray(f_lax), np.asarray(f_k)), (
                "v2 fused path lost bit-identity at "
                f"C={C} chunk={chunk}")
            entries.append(_scan_entry(
                "kernel/v2_fused", C, t_k, chunk=chunk,
                derived=f"x{t_lax / t_k:.2f}_vs_lax"))

        tuned = autotune.tuned_chunk(C, measure=True)
        t_t, (f_t, _) = timed(
            lambda: simulate_completion_scan_jit(
                *args, use_kernel=True, kernel_chunk=tuned), repeats=3)
        assert np.array_equal(np.asarray(f_lax), np.asarray(f_t))
        entries.append(_scan_entry(
            "kernel/v2_tuned", C, t_t, chunk=tuned,
            derived=f"x{t_lax / t_t:.2f}_vs_lax"))

    # legacy v1 kernel in isolation (tolerance-equivalent; interpret mode
    # only, so it is compared only where the kernels are interpreted)
    from repro.kernels.seg_scan.ops import segmented_cumsum, segmented_cumsum_v2

    rng = np.random.default_rng(1)
    term = jnp.asarray(rng.uniform(0, 5, v1_size).astype(np.float32))
    start = jnp.asarray(rng.uniform(size=v1_size) < 0.1)
    for chunk in (chunks if path == "interpret" else ()):
        t_v1, _ = timed(segmented_cumsum, term, start.astype(jnp.float32),
                        chunk=chunk, repeats=2)
        entries.append(_scan_entry("kernel/v1", v1_size, t_v1, chunk=chunk))
        t_v2, _ = timed(segmented_cumsum_v2, term, start, chunk=chunk,
                        repeats=2)
        entries.append(_scan_entry("kernel/v2", v1_size, t_v2, chunk=chunk,
                                   derived=f"x{t_v1 / t_v2:.1f}_vs_v1"))

    ranking = [
        {"chunk": s.chunk, "t_model_s": s.t_model, "bottleneck": s.bottleneck}
        for s in autotune.rank_chunks(sizes[-1])]
    choice = autotune.tuning_report(sizes[-1])
    return {
        "backend": jax.default_backend(),
        "kernel_path": path,
        "note": ("kernel timings are interpret/emulation-mode (no TPU in "
                 "this environment) — NOT compiled-kernel performance"
                 if path == "interpret" else "compiled Pallas kernels"),
        "autotuner": {
            "analytic_ranking": ranking,
            "choice": None if choice is None else {
                "chunk": choice.chunk, "source": choice.source,
                "measured_s": {str(k): v
                               for k, v in choice.measured_s.items()}},
        },
        "entries": entries,
    }


if __name__ == "__main__":
    main()
