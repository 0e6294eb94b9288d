"""Benchmark harness — one function per paper table/figure.

  table3  — dataset work statistics            (paper Table III)
  fig8    — SpGEMM speedups over scl-hash      (paper Figure 8)
  fig9    — spz execution-time breakdown       (paper Figure 9)
  fig10   — chunk-traffic: esc vs spz          (paper Figure 10 analogue)
  fig11   — dynamic mssort/mszip counts        (paper Figure 11)
  table4  — area table + TPU overhead model    (paper Table IV analogue)
  moe     — zipper MoE dispatch microbenchmark (framework integration)
  kernels — stream sort/merge kernel timings   (per-kernel perf)
  dispatch— engine-registry auto selection + batched execution path
  model   — learned-dispatch offline eval (LOBO regret vs oracle)

Prints ``name,us_per_call,derived`` CSV rows per the repo convention, and
writes one machine-readable ``BENCH_<section>.json`` per section run (the
CI benchmark-smoke artifact).
Run everything: PYTHONPATH=src python -m benchmarks.run
Subset:         PYTHONPATH=src python -m benchmarks.run fig8 fig11 --fast
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import numpy as np

from benchmarks import datasets
from repro.core import spgemm_engines as sg
from repro.launch.compile_cache import enable_compile_cache

# rows of the section currently running; flushed to BENCH_<section>.json
_ROWS: list[dict] = []


def _time_call(fn, repeat=1):
    best = float("inf")
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def _emit(name, seconds, derived=""):
    print(f"{name},{seconds * 1e6:.1f},{derived}")
    _ROWS.append({"name": name, "us_per_call": round(seconds * 1e6, 1),
                  "derived": derived})


def _flush_json(section: str) -> None:
    path = f"BENCH_{section}.json"
    with open(path, "w") as f:
        json.dump({"section": section, "rows": _ROWS}, f, indent=1)
    _ROWS.clear()
    print(f"# wrote {path}")


# ---------------------------------------------------------------------------

def table3(mats, fast=False):
    print("# table3: name,us_per_call,nnz|density|avg_work|group_var")
    for name, A in mats:
        t, stats = _time_call(lambda: sg.work_stats(A, A))
        _emit(f"table3.{name}", t,
              f"nnz={stats['nnz']}|dens={stats['density']:.2e}|"
              f"work={stats['avg_work_per_row']:.1f}|"
              f"var={stats['work_var_per_group']:.2f}")
    if fast:
        return  # the spz driver comparison is minutes of host-driver time
    # host vs device-resident spz driver (the PR-3 before/after): same
    # engine semantics, so outputs must be BIT-identical between drivers
    # and structure-identical vs the scl-array oracle (values there differ
    # only by the oracle's float64 accumulation).
    print("# table3: spz host driver vs fused device-resident driver")
    # warm the host driver's chunk kernels once: their shapes are
    # matrix-independent by design (pow2 cap_s buckets), so this keeps
    # XLA compile time out of every matrix's host timing
    sg.spgemm_spz(mats[0][1], mats[0][1], R=16, backend="xla", driver="host")
    for name, A in mats:
        oracle = sg.spgemm_scl_array(A, A)
        t_host, (out_h, st_h) = _time_call(
            lambda: sg.spgemm_spz(A, A, R=16, backend="xla", driver="host"))
        sg.spgemm_spz(A, A, R=16, backend="xla", driver="fused")  # warm jits
        t_fused, (out_f, st_f) = _time_call(
            lambda: sg.spgemm_spz(A, A, R=16, backend="xla", driver="fused"),
            repeat=3)
        nnz = int(np.asarray(out_f.indptr)[-1])
        ident_host = (
            np.array_equal(np.asarray(out_h.indptr), np.asarray(out_f.indptr))
            and np.array_equal(np.asarray(out_h.indices)[:nnz],
                               np.asarray(out_f.indices)[:nnz])
            and np.array_equal(np.asarray(out_h.data)[:nnz],
                               np.asarray(out_f.data)[:nnz]))
        o_nnz = int(np.asarray(oracle.indptr)[-1])
        struct_oracle = (
            np.array_equal(np.asarray(oracle.indptr),
                           np.asarray(out_f.indptr))
            and np.array_equal(np.asarray(oracle.indices)[:o_nnz],
                               np.asarray(out_f.indices)[:nnz]))
        stats_match = (st_h.n_mszip == st_f.n_mszip
                       and st_h.zip_elems == st_f.zip_elems
                       and st_h.n_mssort == st_f.n_mssort)
        _emit(f"table3.spz-host.{name}", t_host,
              f"n_mszip={st_h.n_mszip}|zip_elems={st_h.zip_elems}")
        _emit(f"table3.spz-fused.{name}", t_fused,
              f"speedup_vs_host={t_host / t_fused:.2f}|"
              f"bit_identical_vs_host={ident_host}|"
              f"structure_identical_vs_scl_array={struct_oracle}|"
              f"stats_match={stats_match}")


def fig8(mats, fast=False):
    print("# fig8: impl.matrix,us_per_call,speedup_vs_scl_hash")
    rows = {}
    for name, A in mats:
        res = {}
        res["scl-hash"], _ = _time_call(lambda: sg.spgemm_scl_hash(A, A))
        res["scl-array"], _ = _time_call(lambda: sg.spgemm_scl_array(A, A))
        cap = int(sg.row_work(A, A).sum())
        _ = sg.spgemm_esc(A, A, cap)  # warm the jit cache
        res["vec-radix(esc)"], _ = _time_call(
            lambda: sg.spgemm_esc(A, A, cap), repeat=3)
        if not fast:
            res["spz"], _ = _time_call(
                lambda: sg.spgemm_spz(A, A, R=16, backend="xla",
                                      driver="host")[0])
            res["spz-rsort"], _ = _time_call(
                lambda: sg.spgemm_spz(A, A, R=16, rsort=True, backend="xla",
                                      driver="host")[0])
            sg.spgemm_spz(A, A, R=16, backend="xla", driver="fused")  # warm
            res["spz-fused"], _ = _time_call(
                lambda: sg.spgemm_spz(A, A, R=16, backend="xla",
                                      driver="fused")[0], repeat=3)
        base = res["scl-hash"]
        for impl, t in res.items():
            _emit(f"fig8.{impl}.{name}", t, f"speedup={base / t:.2f}")
        rows[name] = res
    # geomean speedups (the paper's headline numbers)
    for impl in next(iter(rows.values())).keys():
        sp = [rows[n]["scl-hash"] / rows[n][impl] for n in rows]
        gm = float(np.exp(np.mean(np.log(sp))))
        _emit(f"fig8.geomean.{impl}", 0.0, f"speedup={gm:.2f}")


def fig9(mats):
    print("# fig9: spz phase breakdown (fractions of total)")
    for name, A in mats:
        for label, rsort in (("spz", False), ("spz-rsort", True)):
            # host driver: the only one with a per-phase wall-clock split
            _, stats = sg.spgemm_spz(A, A, R=16, rsort=rsort, backend="xla",
                                     driver="host")
            tot = (stats.t_preprocess + stats.t_expand + stats.t_sort +
                   stats.t_output) or 1e-9
            _emit(f"fig9.{label}.{name}", tot,
                  f"pre={stats.t_preprocess / tot:.2f}|"
                  f"expand={stats.t_expand / tot:.2f}|"
                  f"sort={stats.t_sort / tot:.2f}|"
                  f"out={stats.t_output / tot:.2f}")


def fig10(mats):
    """Memory-traffic proxy: tuples moved per element (the paper measures
    L1D accesses). ESC (vec-radix): expansion (1 write) + 32-bit LSD radix
    sort = 4 passes x (read + scattered write) over the full product list
    + compression pass = ~10 tuple-movements per expanded tuple, with the
    scattered writes spanning cache lines (the effect Figure 10 shows).
    spz: every tuple is touched once per sort chunk + once per surviving
    merge round (duplicates drop out early), all unit-stride."""
    print("# fig10: traffic esc_elems vs spz chunk loads+stores")
    for name, A in mats:
        work = int(sg.row_work(A, A).sum())
        esc_elems = 10 * work
        _, st = sg.spgemm_spz(A, A, R=16, backend="xla", driver="host")
        spz_elems = st.sort_elems + st.zip_elems
        _emit(f"fig10.{name}", 0.0,
              f"esc_elems={esc_elems}|spz_elems={spz_elems}|"
              f"reduction={esc_elems / max(1, spz_elems):.2f}x")


def fig11(mats):
    # S=64 (4 lock-step groups of 16 batched per issue) keeps the python
    # driver tractable; instruction-count *ratios* match the S=16 ISA since
    # counts scale with ceil(rows/S) x per-group iterations either way.
    print("# fig11: dynamic mssortk+mszipk instruction counts")
    for name, A in mats:
        _, s0 = sg.spgemm_spz(A, A, R=16, S=64, backend="xla", driver="host")
        _, s1 = sg.spgemm_spz(A, A, R=16, S=64, rsort=True, backend="xla",
                              driver="host")
        _emit(f"fig11.{name}", 0.0,
              f"spz={s0.n_mssort + s0.n_mszip}|"
              f"rsort={s1.n_mssort + s1.n_mszip}|"
              f"reduction={(s0.n_mssort + s0.n_mszip) / max(1, s1.n_mssort + s1.n_mszip):.2f}x")


def table4():
    """Paper Table IV (12nm post-synthesis) transcription + the TPU-side
    cost model of the zipper primitives (see DESIGN.md §7)."""
    print("# table4: component,area_kum2,count_base|count_spz")
    rows = [
        ("baseline_PE", 0.45, "x256|-"),
        ("sparsezipper_PE", 0.51, "-|x256"),
        ("skew_buffer_16lane", 3.16, "x2|x2"),
        ("deskew_buffer_16lane", 3.16, "x1|x2"),
        ("matrix_register_16x512b", 0.96, "x16|x16"),
        ("popcount_logic", 0.45, "-|x1"),
    ]
    for n, a, c in rows:
        _emit(f"table4.{n}", 0.0, f"area={a}|{c}")
    base = 0.45 * 256 + 3.16 * 2 + 3.16 + 0.96 * 16
    spz = 0.51 * 256 + 3.16 * 2 + 3.16 * 2 + 0.96 * 16 + 0.45
    _emit("table4.total", 0.0,
          f"base={base:.1f}|spz={spz:.1f}|overhead={100 * (spz / base - 1):.2f}%")
    # TPU-side: zipper sort/merge cost per chunk relative to an MXU matmul
    R = 128
    sort_stages = sum(range(1, R.bit_length()))        # log^2 network
    merge_stages = (2 * R).bit_length() - 1
    _emit("table4.tpu_model", 0.0,
          f"R={R}|sort_stages={sort_stages}|merge_stages={merge_stages}|"
          "compress=1xMXU_128x128_matmul")


def moe_bench():
    print("# moe: zipper dispatch vs einsum dispatch (CPU wall time)")
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import base as cb
    from repro.models import moe as moe_mod
    cfg = dataclasses.replace(cb.get_smoke_config("arctic_480b"),
                              d_model=128, num_experts=16, top_k=2,
                              moe_d_ff=256, capacity_factor=1.5)
    key = jax.random.PRNGKey(0)
    p = moe_mod.moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(key, (8, 512, cfg.d_model), jnp.float32)
    for disp in ("einsum", "zipper"):
        fn = jax.jit(lambda p, x: moe_mod.moe_block(p, x, cfg,
                                                    dispatch=disp)[0])
        fn(p, x).block_until_ready()
        t, _ = _time_call(lambda: fn(p, x).block_until_ready(), repeat=5)
        _emit(f"moe.{disp}", t, f"tokens_per_s={8 * 512 / t:.0f}")


def kernels_bench():
    print("# kernels: stream sort/merge (pallas-interpret vs xla oracle)")
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    S, R = 256, 128
    keys = jnp.asarray(rng.integers(0, 64, (S, R)).astype(np.int32))
    vals = jnp.asarray(rng.standard_normal((S, R)).astype(np.float32))
    lens = jnp.asarray(rng.integers(0, R, S).astype(np.int32))
    for bk in ("xla", "pallas"):
        def fn():
            return ops.stream_sort(keys, vals, lens,
                                   backend=bk)[0].block_until_ready()
        fn()
        t, _ = _time_call(fn, repeat=3)
        _emit(f"kernels.stream_sort.{bk}", t,
              f"streams={S}|R={R}|Melem_per_s={S * R / t / 1e6:.1f}")


def dispatch_bench(mats, fast=False):
    """Engine-registry section: per-matrix auto selection (heuristic rule +
    chosen engine + the features that drove it), auto-dispatch wall time,
    and the batched single-compilation path vs lane-at-a-time execution."""
    from repro.core import dispatch as dp
    from repro.core.formats import batch_csr, random_sparse
    print("# dispatch: auto-selection + batched single-compilation path")
    # fresh private cache: measure selection, not a previous run's plans
    cache = dp.AutotuneCache(os.path.join(
        tempfile.mkdtemp(prefix="bench_autotune_"), "cache.json"))
    for name, A in mats:
        dp.clear_feature_cache()
        t_sel, info = _time_call(lambda: dp.explain(A, A))
        t_sel_hit, _ = _time_call(lambda: dp.explain(A, A), repeat=3)
        f = info["features"]
        # selection-only row: emitted in BOTH modes under its own name so
        # the CI --fast run and the committed full-mode baselines compare
        # like with like (a full auto multiply is too slow for the smoke
        # lane and gets its own dispatch.auto row below)
        _emit(f"dispatch.select.{name}", t_sel,
              f"engine={info['engine']}|rule={info['rule']}|"
              f"select_cached_us={t_sel_hit * 1e6:.1f}|"
              f"dens={f['density']:.2e}|var={f['work_var_per_group']:.2f}")
        if not fast:
            t, _ = _time_call(lambda: dp.spgemm(A, A, engine="auto",
                                                cache=cache), repeat=2)
            _emit(f"dispatch.auto.{name}", t,
                  f"engine={info['engine']}|rule={info['rule']}")
    # end-to-end engine rows on the first matrix (cached-plan serving path)
    A = mats[0][1]
    dp.spgemm(A, A, engine="esc")  # warm
    t, _ = _time_call(lambda: dp.spgemm(A, A, engine="esc"))
    _emit("dispatch.exec.esc", t, f"matrix={mats[0][0]}")
    # per-kernel-backend rows: the backend is a planned dimension, so the
    # same engine runs under each registered on-device backend (off-TPU
    # the pallas tier runs in interpret mode — labelled accordingly);
    # the xla timing doubles as the legacy dispatch.exec.spz-fused row
    import jax
    from repro.core import stream as kvstream
    from repro.core.formats import EMPTY
    # synthetic (S, L, R) work bucket for the stage-level kernel rows:
    # unsorted product streams for the fused pipeline, plus two sorted
    # unique EMPTY-padded partitions for the native merge kernel
    S, R, C = 8, 16, 4
    L = C * R
    rng = np.random.default_rng(7)
    b_keys = rng.integers(0, 4096, size=(S, L)).astype(np.int32)
    b_vals = rng.standard_normal((S, L)).astype(np.float32)
    b_lens = rng.integers(L // 2, L + 1, size=S).astype(np.int32)
    b_keys[np.arange(L)[None, :] >= b_lens[:, None]] = EMPTY

    def _sorted_side(seed):
        r = np.random.default_rng(seed)
        k = np.full((S, L), EMPTY, np.int32)
        v = np.zeros((S, L), np.float32)
        lens = r.integers(0, L + 1, size=S).astype(np.int32)
        for i, n in enumerate(lens):
            k[i, :n] = np.sort(r.choice(4096, size=n, replace=False))
            v[i, :n] = r.standard_normal(n)
        return k, v, lens

    mka, mva, mla = _sorted_side(1)
    mkb, mvb, mlb = _sorted_side(2)
    for bk in ("xla", "pallas"):
        label = bk if (bk != "pallas" or jax.default_backend() == "tpu") \
            else "pallas-interpret"
        reps = 1 if bk == "pallas" else 3
        dp.spgemm(A, A, engine="spz-fused", R=16, backend=bk)  # warm
        t_bk, _ = _time_call(
            lambda: dp.spgemm(A, A, engine="spz-fused", R=16, backend=bk),
            repeat=reps)
        if bk == "xla":
            _emit("dispatch.exec.spz-fused", t_bk, f"matrix={mats[0][0]}")
        _emit(f"dispatch.exec.spz-fused/{label}", t_bk,
              f"matrix={mats[0][0]}|backend={bk}")
        # stage rows: the device-resident merge primitive and the whole
        # sort+merge-tree bucket (pallas runs its single-kernel
        # fused_bucket; xla composes chunk_sort + the XLA merge tree),
        # jitted end-to-end the way the spz driver issues them
        merge_fn = jax.jit(
            lambda ka, va, la, kb_, vb, lb: kvstream.merge_partitions(
                ka, va, la, kb_, vb, lb, R=R, backend=bk)[0])
        fused_fn = jax.jit(
            lambda k, v, n: kvstream.fused_sort_merge(
                k, v, n, R=R, backend=bk)[0])

        def _merge():
            return merge_fn(mka, mva, mla, mkb, mvb,
                            mlb).block_until_ready()

        def _fused():
            return fused_fn(b_keys, b_vals, b_lens).block_until_ready()

        _merge()
        t_m, _ = _time_call(_merge, repeat=reps)
        _emit(f"dispatch.exec.spz-fused/{label}.merge", t_m,
              f"streams={S}|L={L}|R={R}|backend={bk}")
        _fused()
        t_f, _ = _time_call(_fused, repeat=reps)
        _emit(f"dispatch.exec.spz-fused/{label}.fused-bucket", t_f,
              f"streams={S}|L={L}|R={R}|C={C}|backend={bk}|"
              f"single_kernel={bk == 'pallas'}")
    # batched path: ragged request batch, one compilation across lanes
    lanes = [random_sparse(256, 256, d, seed=i)
             for i, d in enumerate((0.005, 0.01, 0.02, 0.04))]
    A = batch_csr(lanes, batch_cap=len(lanes))
    works = [int(sg.row_work(m, m).sum()) for m in lanes]
    cap = 1 << max(16, (max(works) - 1).bit_length())
    dp.spgemm_batched(A, A, engine="esc", cap_products=cap)  # warm the jit
    t_b, _ = _time_call(
        lambda: dp.spgemm_batched(A, A, engine="esc", cap_products=cap),
        repeat=2 if fast else 3)
    for m in lanes:
        sg.spgemm_esc(m, m, cap_products=cap)  # warm per-lane jit
    t_s, _ = _time_call(
        lambda: [sg.spgemm_esc(m, m, cap_products=cap) for m in lanes],
        repeat=2 if fast else 3)
    _emit("dispatch.batched.esc", t_b,
          f"lanes={len(lanes)}|sequential_us={t_s * 1e6:.1f}|"
          f"speedup={t_s / t_b:.2f}")
    if not fast:
        t_z, _ = _time_call(
            lambda: dp.spgemm_batched(A, A, engine="spz-host", R=16,
                                      backend="xla"))
        _emit("dispatch.batched.spz", t_z, f"lanes={len(lanes)}")
        dp.spgemm_batched(A, A, engine="spz-fused", R=16, backend="xla")  # warm
        t_zf, _ = _time_call(
            lambda: dp.spgemm_batched(A, A, engine="spz-fused", R=16,
                                      backend="xla"), repeat=3)
        _emit("dispatch.batched.spz-fused", t_zf,
              f"lanes={len(lanes)}|speedup_vs_host={t_z / t_zf:.2f}")


def model_bench(fast=False):
    """Learned-dispatch section: build a measurement dataset with autotune
    sweeps over a synthetic regime grid, replay the cached timings offline
    with leave-one-bucket-out splits (regret vs. oracle, selection accuracy
    vs. the heuristic table), and measure the model plan path against the
    cached-plan budget."""
    from repro.core import dispatch as dp
    from repro.core.formats import random_sparse
    from repro.models import dispatch_model as dm
    print("# model: learned dispatch — dataset, LOBO replay, plan budget")
    cache = dp.AutotuneCache(os.path.join(
        tempfile.mkdtemp(prefix="bench_model_"), "cache.json"))
    # dataset: one autotune sweep per (size, density) regime; every sweep
    # logs its full per-candidate timing vector + features into the cache
    sizes = (32, 48, 64, 96, 128, 192) if fast \
        else (32, 48, 64, 96, 128, 192, 256, 384)
    densities = (0.005, 0.02)
    t0 = time.perf_counter()
    n_sweeps = 0
    for i, n in enumerate(sizes):
        for j, dens in enumerate(densities):
            A = random_sparse(n, n, dens, seed=10 * i + j)
            B = random_sparse(n, n, dens, seed=500 + 10 * i + j)
            dp.plan(A, B, autotune=True, cache=cache, model=False)
            n_sweeps += 1
    t_ds = time.perf_counter() - t0
    samples = dm.samples_from_entries(cache.entries())
    _emit("model.dataset", t_ds,
          f"buckets={len(samples)}|sweeps={n_sweeps}")
    # leave-one-bucket-out replay: train on all-but-one bucket, select on
    # the held-out one, score against the bucket's own measured timings.
    # The heuristic comparator is scored generously: its engine pick is
    # charged the *best* measured time over that engine's backends.
    steps = 150 if fast else 300
    t0 = time.perf_counter()
    reg_m, reg_h, acc_m, acc_h = [], [], 0, 0
    for i, s in enumerate(samples):
        m = dm.DispatchModel.train(samples[:i] + samples[i + 1:],
                                   steps=steps)
        t = s["timings"]
        oracle = min(t, key=t.get)
        sel = m.select(s["features"], allowed=set(t))
        mc = sel.combo if sel is not None else oracle
        eng_h, _ = dp.choose_engine(s["features"], dp.DEFAULT_HEURISTICS)
        h_times = [v for c, v in t.items()
                   if dp.split_combo(c)[0] == eng_h]
        th = min(h_times) if h_times else max(t.values())
        reg_m.append(t[mc] / t[oracle] - 1.0)
        reg_h.append(th / t[oracle] - 1.0)
        acc_m += int(mc == oracle)
        acc_h += int(eng_h == dp.split_combo(oracle)[0])
    t_eval = time.perf_counter() - t0
    folds = max(1, len(samples))
    _emit("model.regret_vs_oracle", t_eval,
          f"regret_model={float(np.mean(reg_m)):.4f}|"
          f"regret_heuristic={float(np.mean(reg_h)):.4f}|"
          f"acc_model={acc_m / folds:.3f}|acc_heuristic={acc_h / folds:.3f}|"
          f"folds={len(samples)}")
    # final model on the full dataset, persisted next to the cache file —
    # exactly what an offline (re)train job produces
    t_tr, model = _time_call(lambda: dm.train_and_save(
        cache.entries(), dp.model_path_for(cache), steps=steps))
    _emit("model.train", t_tr,
          f"samples={model.n_samples}|candidates={len(model.candidates)}|"
          f"sigma={model.sigma:.3f}|version={model.version}")
    # plan-time budget: the model path (unseen bucket, floor pinned to 0
    # so every call takes the prediction instead of writing a heuristic
    # entry) vs the cached-plan path.  Same shape for both pairs — only
    # the nnz bucket differs — so the comparison isolates selection cost
    # from the shared per-plan work (operand validation, kwarg
    # resolution).
    A = random_sparse(80, 80, 0.03, seed=777)
    B = random_sparse(80, 80, 0.03, seed=778)
    conf = dp.explain(A, B, cache=cache)["model"]["confidence"]
    art = dm.DispatchModel.load(dp.model_path_for(cache))
    art.confidence_floor = 0.0
    p = dp.plan(A, B, cache=cache, model=art)
    t_model, _ = _time_call(lambda: dp.plan(A, B, cache=cache, model=art),
                            repeat=20)
    A0 = random_sparse(80, 80, 0.01, seed=888)
    B0 = random_sparse(80, 80, 0.01, seed=889)
    dp.plan(A0, B0, autotune=True, cache=cache, model=False)  # seed entry
    t_cached, _ = _time_call(
        lambda: dp.plan(A0, B0, cache=cache, model=False), repeat=20)
    _emit("model.select_us", t_model,
          f"cached_us={t_cached * 1e6:.1f}|"
          f"select_budget_ratio={t_model / t_cached:.2f}|"
          f"source={p.source}|confidence={conf:.3f}")


def serve_bench(fast=False):
    """Continuous-serving section: synthetic mixed SpGEMM traffic through
    the bucketed service (serving/spgemm_service.py) on the sharded
    plan/execute path.  Reports warmup vs steady-state request rate,
    latency percentiles, and the autotune-cache plan hit rate — the
    serving steady state the dispatch caches exist for.  The async phase
    (PR 9) measures the compile-ahead + async-flush pipeline: warm hit
    rate on the first post-warm flush wave, then open-loop paced tail
    latency with flushes on an executor, then coordinator pools under
    concurrent submitter threads."""
    from repro.core import dispatch as dp
    from repro.launch.serve_spgemm import make_traffic
    from repro.serving.spgemm_service import SpGemmService
    print("# serve: bucketed continuous service, warmup vs steady state")
    n = 96 if fast else 240
    cache = dp.AutotuneCache(os.path.join(
        tempfile.mkdtemp(prefix="bench_serve_"), "autotune.json"))
    dp.clear_feature_cache()
    service = SpGemmService(max_batch=8, flush_timeout=0.05, engine="auto",
                            cache=cache)
    traffic = make_traffic(n, seed=0)
    warmup = n // 4
    t0 = time.perf_counter()
    for A, B in traffic[:warmup]:
        service.submit(A, B)
        service.pump()
    service.drain()
    t_warm = time.perf_counter() - t0
    warm = service.stats()  # warmup-window stats, before the steady phase
    snap = (len(service.completed), len(service.flush_log))
    t1 = time.perf_counter()
    for A, B in traffic[warmup:]:
        service.submit(A, B)
        service.pump()
    service.drain()
    t_steady = time.perf_counter() - t1
    steady = service.stats(since_request=snap[0], since_flush=snap[1])
    _emit("serve.warmup", t_warm / max(1, warmup),
          f"reqs={warmup}|req_per_s={warmup / t_warm:.1f}|"
          f"hit_rate={warm['plan_hit_rate']:.2f}")
    _emit("serve.steady", t_steady / max(1, n - warmup),
          f"reqs={n - warmup}|req_per_s={(n - warmup) / t_steady:.1f}|"
          f"p50_us={steady['p50_latency_s'] * 1e6:.1f}|"
          f"p95_us={steady['p95_latency_s'] * 1e6:.1f}|"
          f"hit_rate={steady['plan_hit_rate']:.2f}|"
          f"flushes={steady['n_flushes']}|buckets={steady['n_buckets']}")

    # -- chaos phase: same traffic under a 10% kernel-fault rate plus a
    # one-shot worker kill; the availability row is the PR-6 resilience
    # gate (>= 0.99 expected: retries + worker re-bucketing + isolation)
    from repro.distributed.spgemm_shard import kill_worker_spec
    from repro.runtime import faultinject as fi
    n_chaos = 48 if fast else 120
    chaos_service = SpGemmService(
        max_batch=8, flush_timeout=0.05, engine="auto", cache=cache,
        policy=dp.RetryPolicy(max_attempts=3, backoff_base_s=0.0))
    t2 = time.perf_counter()
    with fi.injected(fi.FaultSpec(site="kernel.batched", kind="raise",
                                  rate=0.10),
                     kill_worker_spec(0), seed=7):
        for A, B in make_traffic(n_chaos, seed=1):
            chaos_service.submit(A, B)
            chaos_service.pump()
        chaos_service.drain()
    t_chaos = time.perf_counter() - t2
    cs = chaos_service.stats()
    _emit("serve.chaos.availability", t_chaos / max(1, n_chaos),
          f"reqs={n_chaos}|availability={cs.get('availability', 1.0):.4f}|"
          f"dead_letters={cs['n_dead_letters']}|degraded={cs['n_degraded']}|"
          f"retry_flush_rate={cs.get('flush_retry_rate', 0.0):.2f}")
    # p50_degraded_us only exists when degraded requests exist — a chaos
    # run lucky enough to serve everything planned must not report the
    # planned p50 as a fake "degraded" latency (compare_baselines skips
    # rows whose baseline us_per_call is 0, so the timing gate tolerates
    # either shape)
    degraded_p50 = cs.get("p50_latency_degraded_s", 0.0)
    degraded_info = f"n_degraded={cs['n_degraded']}|" \
                    f"p50_planned_us={cs.get('p50_latency_s', 0.0) * 1e6:.1f}"
    if cs["n_degraded"]:
        degraded_info += f"|p50_degraded_us={degraded_p50 * 1e6:.1f}"
    _emit("serve.chaos.degraded", degraded_p50, degraded_info)

    # -- async + compile-ahead phase (PR 9): pad buckets of the traffic
    # mix pre-compiled before the first request (PlanWarmer), flushes on
    # an executor so admission never blocks.  The warm row gates the
    # first post-warm flush wave (every bucket's first real flush should
    # land on a pre-compiled computation); the p50/p95 rows measure an
    # open-loop paced steady state — per-request latency is the real
    # wall clock from submit to completion, so these are the tail rows
    # the synchronous serve.steady p50 (~1.7 s with inline compiles)
    # is compared against.
    import threading

    from repro.core.formats import random_sparse
    from repro.launch.serve_spgemm import TRAFFIC_MIX
    from repro.serving.plan_warmer import PlanWarmer
    n_async = 48 if fast else 96
    a_cache = dp.AutotuneCache(os.path.join(
        tempfile.mkdtemp(prefix="bench_serve_async_"), "autotune.json"))
    reps = [(random_sparse(nn, nn, dd, seed=7 + i, pattern=pp),) * 2
            for i, (nn, dd, pp) in enumerate(TRAFFIC_MIX)]
    warmer = PlanWarmer(configured=reps)
    a_service = SpGemmService(max_batch=4, flush_timeout=0.02,
                              engine="auto", cache=a_cache,
                              async_flushes=2, warmer=warmer)
    t0 = time.perf_counter()
    a_service.prewarm()
    t_prewarm = time.perf_counter() - t0
    wave = 24 if fast else 36

    def _paced(n_reqs, seed, pace):
        for A, B in make_traffic(n_reqs, seed=seed):
            t_next = time.perf_counter() + pace
            a_service.submit(A, B)
            while time.perf_counter() < t_next:
                a_service.pump()
                time.sleep(0.002)
        a_service.drain()

    # first post-warm flush wave: the warm-hit gate — every bucket's
    # first real flush should land on a plan compiled ahead of traffic
    for A, B in make_traffic(wave, seed=11):
        a_service.submit(A, B)
        a_service.pump()
    a_service.drain()
    ws = a_service.stats()
    _emit("serve.warm.hit_rate", t_prewarm / max(1, ws["n_warmed"]),
          f"warmed={ws['n_warmed']}|prewarm_s={t_prewarm:.2f}|"
          f"warm_hit_rate={ws['warm_hit_rate']:.4f}|"
          f"first_wave_reqs={wave}|"
          f"availability={ws.get('availability', 1.0):.4f}")
    # absorption: the plan-level warm covers the jit_key, but the spz
    # lock-step driver compiles per (stream-bucket, chunk) shape under
    # it — a few more waves absorb those residuals before the measured
    # steady window (untimed, like every other bench's warmup; full
    # width even in fast mode, narrow waves leave combos unabsorbed)
    for seed in (13, 15):
        for A, B in make_traffic(36, seed=seed):
            a_service.submit(A, B)
            a_service.pump()
        a_service.drain()
    _paced(36, seed=17, pace=0.15)
    # steady tail latency: open-loop paced arrivals within the warmed
    # flush capacity — per-request latency is real submit-to-completion
    # wall clock, the number the synchronous serve.steady p50 pays
    # compiles inside
    snap = (len(a_service.completed), len(a_service.flush_log))
    pace = 0.12
    _paced(n_async, seed=12, pace=pace)
    a_service.close()
    st = a_service.stats(since_request=snap[0], since_flush=snap[1])
    _emit("serve.async.p50", st["p50_latency_s"],
          f"reqs={n_async}|pace_ms={pace * 1e3:.0f}|"
          f"req_per_s={st['req_per_s']:.1f}|"
          f"warm_hit_rate={st['warm_hit_rate']:.4f}|"
          f"availability={st.get('availability', 1.0):.4f}")
    _emit("serve.async.p95", st["p95_latency_s"],
          f"reqs={n_async}|pace_ms={pace * 1e3:.0f}|"
          f"p50_us={st['p50_latency_s'] * 1e6:.1f}")

    # -- multi-process phase: the same bucketed service dispatching its
    # flushes to a ProcessCoordinator worker pool (runtime/coordinator.py).
    # Throughput rows run one full untimed pass first so per-worker jax
    # import + kernel compile stay out of the timed window; the kill row
    # runs cold so the SIGKILL lands inside the measured traffic.  On a
    # single-core runner the w2/w4 rows measure dispatch overhead, not
    # parallel speedup — the availability fraction is the real gate.
    from repro.runtime.coordinator import (ChipContention,
                                           ProcessCoordinator,
                                           check_one_process_per_chip)
    try:
        check_one_process_per_chip(4)
    except ChipContention as e:
        print(f"# serve.multiproc / serve.async.w*: not run: {e}")
        return
    n_mp = 24 if fast else 48

    def _mp_traffic(pool, path, seed):
        mp = SpGemmService(
            max_batch=8, flush_timeout=0.05, engine="auto",
            cache=dp.AutotuneCache(path), coordinator=pool,
            policy=dp.RetryPolicy(max_attempts=3, backoff_base_s=0.0))
        t0 = time.perf_counter()
        for A, B in make_traffic(n_mp, seed=seed):
            mp.submit(A, B)
            mp.pump()
        mp.drain()
        return mp, time.perf_counter() - t0

    def _mp_pool_run(n_workers, specs=None):
        path = os.path.join(tempfile.mkdtemp(prefix="bench_mp_"),
                            "autotune.json")
        with ProcessCoordinator(n_workers, cache_path=path,
                                fault_specs=specs, fault_seed=5) as pool:
            if specs is None:
                # warm untimed on the SAME stream the timed passes run
                # (a different warm stream leaves spilled buckets
                # uncompiled on their spill worker, and that compile
                # then lands inside the timed window), then take the
                # best of two timed passes — on a shared single-core
                # runner one pass flaps enough to fake an inversion
                _mp_traffic(pool, path, seed=4)
                mp, wall = _mp_traffic(pool, path, seed=4)
                mp2, wall2 = _mp_traffic(pool, path, seed=4)
                if wall2 < wall:
                    mp, wall = mp2, wall2
            else:
                mp, wall = _mp_traffic(pool, path, seed=4)
            return mp, wall, pool.alive_count, \
                [e["event"] for e in pool.events]

    for w in (1, 2, 4):
        mp, wall, alive, _ = _mp_pool_run(w)
        ms = mp.stats()
        _emit(f"serve.multiproc.w{w}", wall / max(1, n_mp),
              f"workers={w}|reqs={n_mp}|req_per_s={n_mp / wall:.1f}|"
              f"availability={ms.get('availability', 1.0):.4f}|"
              f"dead_letters={ms['n_dead_letters']}|alive={alive}")

    # -- concurrent-submitter phase: the same pools driven by two client
    # threads submitting in parallel (the service admission path is
    # thread-safe); bucket-affinity dispatch keeps each pad bucket's
    # flushes on the worker that compiled it, so added workers must not
    # cost throughput (the old w4 < w2 inversion)
    def _mp_concurrent(pool, path, seed, n_sub=2):
        mp_svc = SpGemmService(
            max_batch=8, flush_timeout=0.05, engine="auto",
            cache=dp.AutotuneCache(path), coordinator=pool,
            policy=dp.RetryPolicy(max_attempts=3, backoff_base_s=0.0))
        streams = [make_traffic(n_mp // n_sub, seed=seed + k)
                   for k in range(n_sub)]

        def feed(stream):
            for A, B in stream:
                mp_svc.submit(A, B)
                mp_svc.pump()

        t0 = time.perf_counter()
        threads = [threading.Thread(target=feed, args=(s,))
                   for s in streams]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        mp_svc.drain()
        return mp_svc, time.perf_counter() - t0

    for w in (1, 2, 4):
        path = os.path.join(tempfile.mkdtemp(prefix="bench_mpc_"),
                            "autotune.json")
        with ProcessCoordinator(w, cache_path=path) as pool:
            # warm untimed on the same streams the timed passes run
            _mp_concurrent(pool, path, seed=21)
            mp, wall = _mp_concurrent(pool, path, seed=21)
            mp2, wall2 = _mp_concurrent(pool, path, seed=21)  # best of 2
            if wall2 < wall:
                mp, wall = mp2, wall2
            alive = pool.alive_count
        ms = mp.stats()
        _emit(f"serve.async.w{w}", wall / max(1, n_mp),
              f"workers={w}|submitters=2|reqs={n_mp}|"
              f"req_per_s={n_mp / wall:.1f}|"
              f"availability={ms.get('availability', 1.0):.4f}|"
              f"dead_letters={ms['n_dead_letters']}|alive={alive}")

    mp, wall, alive, events = _mp_pool_run(2, specs={
        0: [fi.FaultSpec(site="service.flush", kind="kill_process",
                         max_fires=1),
            fi.FaultSpec(site="kernel.batched", kind="raise", rate=0.10)],
        1: [fi.FaultSpec(site="kernel.batched", kind="raise", rate=0.10)],
    })
    ks = mp.stats()
    _emit("serve.multiproc.kill", wall / max(1, n_mp),
          f"workers=2|reqs={n_mp}|"
          f"availability={ks.get('availability', 1.0):.4f}|"
          f"dead_letters={ks['n_dead_letters']}|"
          f"worker_lost={events.count('worker_lost')}|"
          f"restarts={events.count('restart')}|alive_at_drain={alive}")


ALL = {"table3": table3, "fig8": fig8, "fig9": fig9, "fig10": fig10,
       "fig11": fig11, "table4": table4, "moe": moe_bench,
       "kernels": kernels_bench, "dispatch": dispatch_bench,
       "model": model_bench, "serve": serve_bench}

_NEEDS_MATS = ("table3", "fig8", "fig9", "fig10", "fig11", "dispatch")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("which", nargs="*", default=list(ALL), choices=list(ALL),
                    metavar="section")
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow spz wall-time runs in fig8/dispatch")
    ap.add_argument("--limit", type=int, default=None,
                    help="first N matrices only")
    args = ap.parse_args()
    enable_compile_cache()
    mats = None
    for name in args.which:
        fn = ALL[name]
        if name in _NEEDS_MATS:
            if mats is None:
                mats = [(n, datasets.build(n))
                        for n in datasets.names(args.limit)]
            if name in ("table3", "fig8", "dispatch"):
                fn(mats, fast=args.fast)
            else:
                fn(mats)
        elif name in ("serve", "model"):
            fn(fast=args.fast)
        else:
            fn()
        _flush_json(name)


if __name__ == "__main__":
    main()
