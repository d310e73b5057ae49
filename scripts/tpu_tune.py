"""On-chip tuning sweep (round 5): one JSON line per experiment.

Run on the real TPU to (a) verify the Pallas Lloyd kernel beats its XLA
form and time the cdist program, (b) find the matmul steady-state MFU
config, (c) measure the moments pass against the HBM roofline. Each
experiment is isolated — a failure prints an error line and the sweep
continues — and the exit code is non-zero when any experiment failed or
the host has no TPU. Usage:

    python scripts/tpu_tune.py [--only cdist,kmeans,matmul,moments,rbf,lm,attn_bwd]

Keep sizes bench-equal so winners can be baked straight into bench.py.
"""

import argparse
import json
import sys
import time

import numpy as np


def _sync(arr):
    import jax

    return jax.block_until_ready(arr)


def _time(fn, repeats=2):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def emit(**kw):
    print(json.dumps(kw), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    failed = []

    def run_guarded(name, fn):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — isolate; the rc says it
            emit(exp=name, error=repr(e))
            failed.append(name)

    def want(name):
        return only is None or name in only

    import jax
    import jax.numpy as jnp

    import heat_tpu as ht

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"tpu_tune: no TPU (default backend is {dev.platform!r})",
              file=sys.stderr)
        return 2
    peak_gflops = ht.chip_peaks(dev.device_kind).bf16_flops / 1e9
    emit(device=dev.device_kind, n=len(jax.devices()))

    # ---------------- cdist: the local XLA program ------------------------
    m, k, reps = 16384, 128, 10
    if want("cdist"):
        x = ht.random.rand(m, k, dtype=ht.float32, split=0)

        def run_xla():
            from heat_tpu.spatial.distance import _local_dist, _quadratic_euclidean

            out = None
            for _ in range(reps):
                out = _local_dist(_quadratic_euclidean, x.larray, x.larray, jnp.float32)
            return _sync(out)

        def bench_cdist():
            run_xla()  # compile
            t = _time(run_xla)
            emit(exp="cdist_xla", gflops=round(reps * 2.0 * m * m * k / t / 1e9, 1),
                 seconds=round(t, 3))

        run_guarded("cdist_xla", bench_cdist)

    # ---------------- rbf fused epilogue ---------------------------------
    if want("rbf"):
        x = ht.random.rand(8192, 128, dtype=ht.float32, split=0)

        def run_rbf():
            out = None
            for _ in range(reps):
                out = ht.spatial.rbf(x, sigma=1.0, quadratic_expansion=True)
            return _sync(out.larray)

        def do_rbf():
            run_rbf()
            t = _time(run_rbf)
            emit(exp="rbf_fused", gflops=round(reps * 2.0 * 8192 * 8192 * 128 / t / 1e9, 1))

        run_guarded("rbf", do_rbf)

    # ---------------- kmeans: pallas lloyd vs XLA ------------------------
    if want("kmeans"):
        ns, d, kc, iters = 2_000_000, 64, 64, 50
        xs = ht.random.randn(ns, d, dtype=ht.float32, split=0)

        def fit(tag, force_xla):
            km = ht.cluster.KMeans(n_clusters=kc, init="random", max_iter=iters,
                                   tol=0.0, random_state=1)
            if force_xla:
                import heat_tpu.cluster.pallas_lloyd as pli

                orig = pli.pallas_lloyd_applicable
                pli.pallas_lloyd_applicable = lambda *a: False
                try:
                    km.fit(xs)
                finally:
                    pli.pallas_lloyd_applicable = orig
            else:
                km.fit(xs)
            return _sync(km.cluster_centers_.larray)

        for tag, force in (("pallas", False), ("xla", True)):
            def do(tag=tag, force=force):
                fit(tag, force)  # compile
                t = _time(lambda: fit(tag, force))
                emit(exp=f"kmeans_{tag}",
                     gflops=round(iters * 4.0 * ns * kc * d / t / 1e9, 1),
                     seconds=round(t, 3))

            run_guarded(f"kmeans_{tag}", do)

        # precision tier of the in-kernel scores dot, on the single-device
        # fit kernel directly (bench shapes; single-chip only — on a
        # multi-chip mesh the estimator dispatches to the sharded variant
        # and a direct single-device call on a sharded buffer would not be
        # comparable)
        from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas

        if ht.get_comm().size > 1:
            emit(exp="kmeans_pallas_prec", skipped="multi-device mesh")
        for prec in (("DEFAULT", "HIGH", "bf16x3")
                     if ht.get_comm().size == 1 else ()):
            def do_lp(prec=prec):
                run = lambda: _sync(lloyd_fit_pallas(
                    xs.larray, xs.larray[:kc], ns, iters, 0.0, precision=prec
                )[0])
                run()
                t = _time(run)
                emit(exp=f"kmeans_pallas_prec_{prec}",
                     gflops=round(iters * 4.0 * ns * kc * d / t / 1e9, 1))

            run_guarded(f"kmeans_prec_{prec}", do_lp)

    # ---------------- matmul steady-state sweep --------------------------
    if want("matmul"):
        from heat_tpu.core.dndarray import DNDarray

        def chain_fn(a, y0, reps_):
            def chain(abuf, ybuf):
                A = DNDarray(abuf, a.shape, a.dtype, a.split, a.device, a.comm, True)
                Y = DNDarray(ybuf, y0.shape, y0.dtype, y0.split, y0.device, y0.comm, True)
                for _ in range(reps_):
                    Y = ht.matmul(A, Y)
                return Y.larray

            return jax.jit(chain)

        for n_, reps_ in ((8192, 30), (8192, 60), (16384, 10), (4096, 100)):
            def do(n_=n_, reps_=reps_):
                ab = (ht.random.rand(n_, n_, dtype=ht.float32, split=0) / float(n_)).astype(ht.bfloat16)
                yb = ht.random.rand(n_, n_, dtype=ht.float32, split=0).astype(ht.bfloat16)
                jc = chain_fn(ab, yb, reps_)
                run = lambda: _sync(jc(ab.larray, yb.larray).astype(jnp.float32))
                run()
                t = _time(run)
                gf = reps_ * 2.0 * n_ ** 3 / t / 1e9
                emit(exp=f"matmul_bf16_n{n_}_r{reps_}", gflops=round(gf, 1),
                     mfu=round(gf / peak_gflops, 3), seconds=round(t, 3))

            run_guarded(f"matmul_{n_}_{reps_}", do)

    # ---------------- lm_step remat-policy comparison --------------------
    if want("lm"):
        import optax

        from heat_tpu.nn import TransformerLM

        (v, dm, nh, nl, b, t, lreps) = (32768, 1024, 16, 12, 8, 1024, 8)
        key = jax.random.PRNGKey(0)
        toks = jax.random.randint(key, (b, t), 0, v, dtype=jnp.int32)

        for pol, bwd in ((None, "two_pass"), ("dots", "two_pass"),
                         (None, "fused"), ("dots", "fused")):
            def do(pol=pol, bwd=bwd):
                lm = TransformerLM(
                    vocab_size=v, d_model=dm, num_heads=nh, num_layers=nl,
                    max_len=t, attn_impl="flash", remat=True,
                    remat_policy=pol, dtype=jnp.bfloat16,
                    flash_bwd_impl=bwd,
                )
                params = lm.init(key, toks)
                opt = optax.adamw(1e-3)
                opt_state = opt.init(params)
                n_params = sum(
                    int(np.prod(l.shape))
                    for path, l in jax.tree_util.tree_leaves_with_path(params)
                    if not any(getattr(k_, "key", None) in ("embed", "pos")
                               for k_ in path)
                )

                def loss_fn(p, tk):
                    lg = lm.apply(p, tk)
                    return optax.softmax_cross_entropy_with_integer_labels(
                        lg[:, :-1].astype(jnp.float32), tk[:, 1:]
                    ).mean()

                @jax.jit
                def steps(p, s, tk):
                    def body(_, carry):
                        p_, s_ = carry
                        _, g = jax.value_and_grad(loss_fn)(p_, tk)
                        u, s_ = opt.update(g, s_, p_)
                        return optax.apply_updates(p_, u), s_

                    return jax.lax.fori_loop(0, lreps, body, (p, s))

                def run():
                    p, _ = steps(params, opt_state, toks)
                    return _sync(jax.tree.leaves(p)[0].astype(jnp.float32))

                run()
                tm = _time(run)
                gf = lreps * 6.0 * n_params * b * t / tm / 1e9
                emit(exp=f"lm_step_remat_{pol or 'full'}_bwd_{bwd}",
                     gflops=round(gf, 1), mfu=round(gf / peak_gflops, 3))

            run_guarded(f"lm_{pol}_{bwd}", do)

    # ---------------- attention backward: fused against two-pass ----------
    # the training cells' shapes (batch, positions, query heads, key-value heads,
    # head size, window) at the tuned tiles, then other tiles at a head of 256;
    # a line holds the forward alone and the forward with its backward, in ms
    if want("attn_bwd"):
        from heat_tpu.parallel import flash_attention

        cells = {
            "glm": (2, 8192, 20, 20, 256, None), "qnext": (2, 8192, 16, 2, 256, None),
            "trinity_full": (1, 16384, 32, 4, 128, None), "trinity_swa": (1, 16384, 32, 4, 128, 2048),
            "lfm2": (2, 8192, 32, 8, 64, None), "olmoe": (4, 4096, 16, 16, 128, None),
        }
        areps = 10
        tuned = [(cell, impl, (None, None)) for cell in cells for impl in ("two_pass", "fused")]
        tiles = [("glm", impl, blks) for impl in ("two_pass", "fused")
                 for blks in ((1024, 1024), (512, 512), (256, 1024), (1024, 512), (512, 2048))]
        for cell, impl, (bq, bk) in tuned + tiles:
            def do_ab(cell=cell, impl=impl, bq=bq, bk=bk):
                b, t, h, h_kv, d, window = cells[cell]
                aq, ak, av = (
                    jax.random.normal(key, (b, t, heads, d), dtype=jnp.bfloat16)
                    for key, heads in zip(jax.random.split(jax.random.PRNGKey(1), 3), (h, h_kv, h_kv))
                )

                def attend(q_, k_, v_):
                    return flash_attention(
                        q_, k_, v_, causal=True, window=window, block_q=bq, block_k=bk, bwd_impl=impl,
                    )

                def chain(step):
                    return jax.jit(lambda q, k, v: jax.lax.fori_loop(0, areps, step, (q, k, v))[0])

                small = jnp.bfloat16(1e-3)
                forward = chain(lambda _, c: (c[0] + attend(*c) * small, c[1], c[2]))
                both = chain(lambda _, c: tuple(
                    a + g * small for a, g in zip(c, jax.grad(
                        lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))(*c))
                ))
                ms = {}
                for name, fn in (("fwd", forward), ("fwd_bwd", both)):
                    run = lambda: _sync(fn(aq, ak, av).astype(jnp.float32))  # noqa: E731
                    run()
                    ms[name] = _time(run, repeats=3) / areps * 1e3
                pairs = t * (t + 1) / 2 if window is None else window * (t - (window - 1) / 2)
                gf = 4.0 * b * h * pairs * d * 2 / 1e9  # four products of the head size a pair: the backward as a model counts it
                bwd = ms["fwd_bwd"] - ms["fwd"]
                emit(exp=f"attn_bwd_{cell}_{impl}_bq{bq}_bk{bk}", fwd_ms=round(ms["fwd"], 3),
                     bwd_ms=round(bwd, 3), bwd_mfu=round(gf / bwd * 1e3 / peak_gflops, 3))

            run_guarded(f"attn_bwd_{cell}_{impl}_{bq}_{bk}", do_ab)

    # ---------------- moments vs HBM roofline ----------------------------
    if want("moments"):
        nm, dm, mreps = 8_000_000, 64, 10
        xm = ht.random.randn(nm, dm, dtype=ht.float32, split=0)

        @jax.jit
        def one_pass(buf):
            from heat_tpu.core.dndarray import DNDarray

            X = DNDarray(buf, xm.shape, xm.dtype, xm.split, xm.device, xm.comm, True)
            return (ht.mean(X, axis=0) + ht.var(X, axis=0)).larray

        def run_m():
            out = None
            for _ in range(mreps):
                out = one_pass(xm.larray)
            return _sync(out)

        def do_m():
            run_m()
            t = _time(run_m)
            gf = mreps * 4.0 * nm * dm / t / 1e9
            bytes_read = mreps * nm * dm * 4
            emit(exp="moments", gflops=round(gf, 1),
                 effective_gbps=round(bytes_read / t / 1e9, 1),
                 note="gbps assumes ONE read of X per pass")

        run_guarded("moments", do_m)

    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
