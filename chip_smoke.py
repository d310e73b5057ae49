"""chip_smoke.py — does the system still start on the chip?

One process, no child process, every chip JAX finds. Drives the main path
once through the entry points a user calls (``import heat_tpu as ht``) at
the full width of the configurations ``bench.py`` measures, checks every
result, and exits 0 only if every stage passed on a TPU. Claims no speed:
wall times are printed as information only, and every stage ends in
``jax.block_until_ready``.

    python chip_smoke.py                 # on a TPU host; anything else fails
    python chip_smoke.py --rehearse-cpu  # tiny sizes, CPU, Pallas interpreter

Stages (one JSON line each, naming platform, device kind and device count):

device   the default backend is a TPU whose ``device_kind`` has a row in the
         peak table (``heat_tpu.chip_peaks``). JAX falls back to the CPU with
         a warning when libtpu fails to initialise, so the assertion is ours.
train    ``TransformerLM`` at the bench width, all 12 layers (vocab 32,768,
         d_model 1,024, 16 heads, batch 8 x 1,024 tokens, bf16, flash
         attention per batch shard, remat) through the path of
         examples/nn/lm_training.py: a ``program_cache.cached_program`` step,
         batch on ``comm.sharding(0, 2)``, AdamW. Five steps on one seeded
         batch: loss finite and lower at the end, nothing traced or compiled
         after step one; every Mosaic call takes one chip's share of the
         batch; on several chips, batch, parameters and optimizer state have
         shards on every chip and per-chip peak memory is of one size.
         Then ``olmoe_1b_7b(num_layers=1)`` at its published widths (625.6 M
         parameters, 4 x 4,096 tokens) on the first chip through
         ``DataParallel.make_train_step``: five steps, loss lower at the
         end, ``moe.dropped`` still 0, and Mosaic calls named ``flash_fwd``
         and ``flash_bwd_*`` in the step. Then one chip's share of
         ``qwen3_next_80b_a3b`` (4 layers, 32 of 512 experts, 18,992 rows of
         the vocabulary, 2 x 8,192 tokens, every block rematerialised): the
         step holds the chunked delta rule (a loop carrying one sequence's
         state, its body the Mosaic calls ``delta_chunk_fwd`` / ``_bwd``) and
         flash kernels that read 2 key-value heads for 16 query
         heads, ``flash_fwd`` once (the block's checkpoint keeps the core's
         output and log-sum-exp: ``attn.kept`` 1), the loss falls,
         ``moe.dropped`` stays 0 against the assignments due on the held
         experts and ``moe.held_share`` is read.
         Then one chip's share of ``trinity_mini`` at the benchmark cell's
         widths (8 layers, 8 of 128 experts, 25,024 rows of the vocabulary,
         1 x 16,384 tokens, every block rematerialised) through
         ``make_train_step(state_rule=balance_bias_rule(0.001))``: the step
         holds the window kernels (``swa_fwd``, ``swa_bwd_fused``)
         beside the full form's, each forward kernel once a
         block (``swa_fwd`` x 6, ``flash_fwd`` x 2, ``attn.kept`` 8), the
         loss falls, ``moe.dropped`` stays 0 and every selection bias has
         moved by the rule's steps.
array    the reference's workloads at bench.py's sizes on split DNDarrays,
         each against a float64 NumPy oracle: mean/var of 8M x 64; 8192^2
         bf16 matmul; cdist and rbf of 16384 x 128 and of a ragged pair
         (1000 x 18 against 2500 x 18), GEMM form; five Lloyd
         iterations of KMeans(64) on 2M x 64; five Lasso sweeps. What mean,
         var, cdist, rbf and KMeans.fit dispatched is read back from JAX's
         own dump of the modules it lowered: mean, var and the fit each hold
         a Mosaic call of their Pallas kernel (the fit two: ``lloyd_update``
         and the final pass ``lloyd_assign``), so no gate sent the call to
         the XLA form; cdist and rbf each lowered one program,
         ``_local_dist``, with no Mosaic call.
kernels  each of the five Pallas kernels lowered at its production block
         sizes with ``interpret`` left to the library, the lowering checked
         for a Mosaic custom call (nothing resolved ``interpret=True``),
         run, and compared with the XLA form it replaces (the Lloyd fit's
         final pass also alone: ``lloyd_assign``'s labels and inertia
         against XLA's on the same centres, at a block multiple and at
         ragged rows with a tail past the last valid one); the flash
         kernels also with 16 query heads on 2 key-value heads of 256
         (forward and both backward forms, against the XLA form on repeated
         K and V), and the gated delta rule at the Qwen3-Next cell's 16 key
         and 32 value heads of 128: the form whose chunk step is the Pallas
         kernel against the recurrence a position at a time, and its five
         gradients against the XLA form's (``delta_rule``, ``delta_rule_bwd``;
         the counters ``gdn.rule.kernel`` / ``gdn.rule.xla`` say which form
         each trace took). ``flash_window``: the sliding-window kernels at the
         Trinity-Mini cell's 32 query heads on 4 of 128, 16,384 positions,
         window 2,048, forward and dq, dk, dv through the model's own
         attention core against the masked XLA form in float32, on normal
         inputs and on the edge probe (the keys exactly 2,047 and 2,048 before
         a query carry its largest scores); the counters
         ``attn.window.kernel`` / ``attn.window.xla`` and
         ``attn.window.blocks_visited`` / ``.blocks_live`` are printed.
serve    an in-process ``ht.serve.Server`` with ``kmeans_predict``
         (bench.py's serving configuration), warmed up; 32 requests; answers
         equal ``km.predict``; nothing compiled after warm-up.

Tolerances. TPU matmuls at default precision round their operands to
bfloat16 (relative 2^-9); the K-family distance GEMMs, cdist among them, and
the Pallas Lloyd kernel use the three-pass bf16 split product (about 2^-16 of
|x||y|). Each check below states the bound it uses and prints the error it
saw. Oracles for matmul and cdist take a row subsample (rows are
independent); moments, KMeans and Lasso are statistics of every row, so
their oracles read the whole array on the host.
"""

import argparse
import gc
import json
import os
import re
import sys
import tempfile
import time
import traceback

import numpy as np

FULL = dict(
    lm=dict(vocab=32768, d_model=1024, heads=16, layers=12, batch=8, seq=1024),
    olmoe=dict(fields={}, batch=4, seq=4096),  # the published widths
    # one chip's share of Qwen3-Next at the published widths: a period of four
    # layers, 32 of 512 experts, an eighth of the vocabulary
    qnext=dict(fields=dict(num_layers=4, experts_held=(0, 32), vocab_size=18992), batch=2, seq=8192),
    # one chip's share of Trinity-Mini as the benchmark's cell cuts it: two periods of
    # the attention pattern, 8 of 128 experts, an eighth of the vocabulary
    trinity=dict(fields=dict(num_layers=8, experts_held=(0, 8), vocab_size=25024), batch=1, seq=16384),
    steps=5,
    moments_rows=8_000_000,
    matmul_n=8192,
    cdist_rows=16384, cdist_k=128, cdist_ragged=(1000, 2500, 18),
    kmeans_rows=2_000_000, kmeans_k=64, iters=5,
    attn_fwd=(4, 4096, 8, 128), attn_bwd=(8, 1024, 16, 64),
    attn_gqa=(1, 2048, 16, 2, 256), rule=(1, 1024, 16, 32, 128),
    attn_window=(16384, 32, 4, 128, 2048),
    kernel_rows=1 << 20, lloyd_rows=1 << 18, int8_n=2048,
    serve_rows=200_000, serve_k=16, requests=32, request_rows=16,
)
# the rehearsal keeps every shape rule (divisible by 4 devices, block
# clamping) and nothing of the size
TINY = dict(
    lm=dict(vocab=256, d_model=64, heads=4, layers=2, batch=8, seq=128),
    olmoe=dict(
        fields=dict(vocab_size=256, d_model=64, num_heads=4, d_ff=32,
                    num_experts=8, experts_per_token=2, max_len=64),
        batch=2, seq=64,
    ),
    qnext=dict(
        fields=dict(num_layers=4, experts_held=(4, 4), vocab_size=256, d_model=64, num_heads=4,
                    num_kv_heads=2, head_dim=32, gdn_key_heads=2, gdn_value_heads=4,
                    gdn_key_dim=16, gdn_value_dim=16, d_ff=32, num_experts=16,
                    experts_per_token=3, shared_d_ff=32, max_len=256),
        batch=2, seq=160,
    ),
    trinity=dict(
        fields=dict(num_layers=8, experts_held=(4, 4), vocab_size=256, d_model=64, embed_scale=8.0, num_heads=4,
                    num_kv_heads=2, head_dim=32, windows=(48, 48, 48, None), dense_d_ff=96, d_ff=32,
                    num_experts=16, experts_per_token=3, shared_d_ff=32, max_len=256),
        batch=1, seq=160,
    ),
    steps=5,
    moments_rows=4096,
    matmul_n=256,
    cdist_rows=512, cdist_k=32, cdist_ragged=(520, 1030, 18),
    kmeans_rows=4096, kmeans_k=8, iters=3,
    attn_fwd=(1, 256, 2, 64), attn_bwd=(1, 256, 2, 64),
    attn_gqa=(1, 256, 4, 2, 64), rule=(1, 160, 1, 2, 16),
    attn_window=(200, 4, 2, 32, 48),
    kernel_rows=2048, lloyd_rows=2048, int8_n=256,
    serve_rows=2048, serve_k=4, requests=8, request_rows=4,
)
FEATURES = 64
ORACLE_ROWS = 64


def _err(got, ref):
    return float(np.max(np.abs(np.asarray(got, np.float64) - ref)))


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _on_all_devices(arr, devices):
    return {s.device for s in arr.addressable_shards} == set(devices)


# -- train --------------------------------------------------------------------


def stage_train(ht, cfg, devices, on_tpu):
    import jax
    import jax.numpy as jnp
    import optax

    from heat_tpu import telemetry
    from heat_tpu.core import program_cache
    from heat_tpu.nn import TransformerLM

    c = cfg["lm"]
    comm = ht.get_comm()
    arch = dict(
        vocab_size=c["vocab"], d_model=c["d_model"], num_heads=c["heads"],
        num_layers=c["layers"], max_len=c["seq"], dtype=jnp.bfloat16,
    )
    lm = TransformerLM(attn_impl="flash", remat=True, comm=comm, **arch)
    opt = optax.adamw(3e-4)
    key = (c["d_model"], c["layers"], c["vocab"])
    replicated = comm.replicated()

    # parameters do not depend on the attention core or the batch: draw them
    # through the XLA twin on one short row, straight onto every chip
    twin = TransformerLM(attn_impl="local", **arch)
    init = program_cache.cached_program(
        "smoke.lm_init", key,
        lambda: lambda k: twin.init(k, jnp.zeros((1, 8), jnp.int32)),
        comm=comm, out_shardings=replicated,
    )
    params = init(jax.random.PRNGKey(0))
    opt_state = program_cache.cached_program(
        "smoke.lm_opt_init", key, lambda: opt.init, comm=comm,
        out_shardings=replicated,
    )(params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))

    def loss_fn(p, toks):
        logits = lm.apply(p, toks)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1].astype(jnp.float32), toks[:, 1:]
        ).mean()

    def step_fn(p, s, toks):
        l, g = jax.value_and_grad(loss_fn)(p, toks)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    step = program_cache.cached_program(
        "smoke.lm_train_step", key, lambda: step_fn, comm=comm,
    )
    toks = np.random.default_rng(0).integers(
        0, c["vocab"], (c["batch"], c["seq"]), dtype=np.int32
    )
    batch = jax.device_put(toks, comm.sharding(0, 2))

    t0 = time.perf_counter()
    params, opt_state, l = step(params, opt_state, batch)
    losses = [float(jax.block_until_ready(l))]
    first_step = time.perf_counter() - t0
    misses = program_cache.stats()["sites"]["smoke.lm_train_step"]["misses"]
    t0 = time.perf_counter()
    with telemetry.CompileWatcher() as w:
        for _ in range(cfg["steps"] - 1):
            params, opt_state, l = step(params, opt_state, batch)
            losses.append(float(jax.block_until_ready(l)))
    later_steps = time.perf_counter() - t0
    site = program_cache.stats()["sites"]["smoke.lm_train_step"]

    _check(all(np.isfinite(losses)), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    _check(
        w.events == 0 and site["misses"] == misses == 1,
        f"compiled after step one: {dict(w.counts)}, registry {site}",
    )
    leaves = jax.tree.leaves((params, opt_state))
    _check(
        _on_all_devices(batch, devices)
        and all(_on_all_devices(l, devices) for l in leaves),
        "batch, parameters or optimizer state miss a chip",
    )
    if on_tpu:
        # flash attention ran per batch shard: every Mosaic call in the
        # step takes batch / chips rows, never the gathered batch
        rows = set(re.findall(
            r"@tpu_custom_call\(.*: \(tensor<(\d+)x",
            step.lower(params, opt_state, batch).as_text(),
        ))
        _check(
            rows == {str(c["batch"] // len(devices))},
            f"Mosaic calls take batches of {sorted(rows)} rows, want "
            f"{c['batch'] // len(devices)} per chip",
        )
    stats = [d.memory_stats() or {} for d in devices]
    peaks = [int(s.get("peak_bytes_in_use", 0)) for s in stats]
    if len(devices) > 1 and all(peaks):
        _check(
            max(peaks) - min(peaks) <= 0.1 * max(peaks),
            f"per-chip peak memory is not of one size: {peaks}",
        )
    del params, opt_state, batch, leaves
    gc.collect()
    return dict(
        params=n_params, losses=[round(v, 4) for v in losses],
        first_step_seconds=round(first_step, 2),
        later_steps_seconds=round(later_steps, 2),
        peak_bytes_in_use=peaks,
        olmoe=_olmoe_steps(cfg, devices, on_tpu),
        qnext=_qnext_steps(cfg, devices, on_tpu),
        trinity=_trinity_steps(cfg, devices, on_tpu),
    )


def _lm_steps(name, model, loss_fn, c, steps, rule=None):
    """``steps`` AdamW steps of ``model`` on its communicator's chip through
    ``DataParallel.make_train_step`` (state donated; ``rule``: its
    ``state_rule``), each step's loss and routing read with ``read_routing``:
    the loss is finite and falls, and ``moe.dropped`` stays 0 against the
    assignments due here (all of them, or those on the experts the model
    holds). Returns what the three models' own checks read: the step's lowered
    text and how many Mosaic calls of each name it holds, the attention cores
    whose residuals the blocks' checkpoints keep (``attn.kept``, counted as the
    step is traced), the losses, the last step's routing, the assignments due,
    what the ``moe.*`` counters gained, and the state the last step returned."""
    import jax
    import jax.numpy as jnp
    import optax

    from heat_tpu import telemetry
    from heat_tpu.core import program_cache
    from heat_tpu.nn import DataParallel, read_routing

    comm = model.comm
    opt = optax.adamw(4e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    step = DataParallel(
        model, comm=comm, optimizer=opt, blocking_parameter_updates=True
    ).make_train_step(loss_fn, has_aux=True, state_rule=rule)
    key = tuple(sorted(c["fields"].items()))

    def init(k):
        drawn = model.clone(attn_impl="local", remat=False).init(k, jnp.zeros((1, 8), jnp.int32))
        return {name: drawn[name] for name in ("params", "route_bias") if name in drawn}

    params = program_cache.cached_program(
        f"smoke.{name}_init", key, lambda: init, comm=comm, out_shardings=comm.replicated(),
    )(jax.random.PRNGKey(0))
    opt_state = program_cache.cached_program(
        f"smoke.{name}_opt_init", key, lambda: lambda p: opt.init({"params": p["params"]}),
        comm=comm, out_shardings=comm.replicated(),
    )(params)
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params["params"]))
    toks = np.random.default_rng(0).integers(
        0, model.vocab_size, (c["batch"], c["seq"]), dtype=np.int32
    )
    counters = telemetry.get_registry().counters
    kept = counters["attn.kept"]
    text = step.lower(params, opt_state, jnp.asarray(toks)).as_text()
    kept = int(counters["attn.kept"] - kept)
    found = re.findall(r'@tpu_custom_call\(.*kernel_name = "(\w+)"', text)
    kernels = {k: found.count(k) for k in sorted(set(found))}
    names = ("moe.dropped", "moe.assignments", "moe.held_share", "moe.steps")
    before = {k: counters[k] for k in names}
    first, count = model.experts_held or (0, model.num_experts)
    losses, due = [], 0
    for _ in range(steps):
        params, opt_state, loss, aux = step(params, opt_state, toks)
        loss, aux = read_routing(loss, aux)  # to the host, and into the moe.* counters
        losses.append(float(loss))
        due += int(aux["expert_counts"][:, first:first + count].sum())
    gained = {k: counters[k] - before[k] for k in names}
    _check(all(np.isfinite(losses)), f"{name} loss not finite: {losses}")
    _check(losses[-1] < losses[0], f"{name} loss did not fall: {losses}")
    _check(
        gained["moe.dropped"] == 0 and gained["moe.assignments"] == due,
        f"{name}: routing dropped {gained['moe.dropped']} of the {due} assignments due here, "
        f"counted {gained['moe.assignments']}",
    )
    del opt_state
    gc.collect()
    return dict(
        params=n_params, losses=[round(v, 4) for v in losses], kernels=kernels, kept=kept, text=text, aux=aux,
        due=due, gained=gained, state=params,
    )


def _olmoe_steps(cfg, devices, on_tpu):
    """``olmoe_1b_7b(num_layers=1)`` on the first chip (:func:`_lm_steps`): all
    ``steps x batch x seq x top-k`` assignments are due, and the attention
    kernels go to Mosaic under the names the benchmark's trace readers look
    for."""
    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.nn import causal_lm_loss, olmoe_1b_7b

    c = cfg["olmoe"]
    model = olmoe_1b_7b(num_layers=1, comm=MeshCommunication(devices=devices[:1]), **c["fields"])  # one chip
    got = _lm_steps(
        "olmoe", model, causal_lm_loss(model, load_balance_coef=0.01, router_z_coef=0.001), c, cfg["steps"]
    )
    kernels = got["kernels"]
    if on_tpu:
        _check(
            "flash_fwd" in kernels
            and any(k.startswith("flash_bwd_") for k in kernels),
            f"no Mosaic call named flash_fwd / flash_bwd_* in the step: {kernels}",
        )
    _check(
        got["due"] == cfg["steps"] * c["batch"] * c["seq"] * model.experts_per_token,
        f"{got['due']} assignments due: not every token's top-k",
    )
    counts = got["aux"]["expert_counts"]
    return dict(
        params=got["params"], losses=got["losses"],
        mosaic_kernels=kernels, load_max_over_mean=round(float(counts.max() / counts.mean()), 3),
    )


def _qnext_steps(cfg, devices, on_tpu):
    """One chip's share of ``qwen3_next_80b_a3b`` on the first chip, every
    block rematerialised (:func:`_lm_steps`): the chip took the chunked delta
    rule (a loop whose carry is the state of one sequence's heads, the chunk
    step a Mosaic call forward and backward) and the flash kernels with the
    key-value heads read by group (compiled Mosaic calls whose K and V operands
    have fewer heads than Q), and ``moe.held_share`` is what the counts give."""
    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.nn import causal_lm_loss, qwen3_next_80b_a3b

    c = cfg["qnext"]
    model = qwen3_next_80b_a3b(comm=MeshCommunication(devices=devices[:1]), remat=True, **c["fields"])
    got = _lm_steps("qnext", model, causal_lm_loss(model, load_balance_coef=0.001), c, cfg["steps"])
    text, kernels = got["text"], got["kernels"]
    state = f"tensor<1x{model.gdn_value_heads}x{model.gdn_key_dim}x{model.gdn_value_dim}xf32>"
    _check(state in text, f"no loop over chunks carrying {state} in the step: the delta rule is not the chunked one")
    if on_tpu:
        _check(
            {"flash_fwd", "flash_bwd_fused", "delta_chunk_fwd", "delta_chunk_bwd"} <= set(kernels),
            f"no Mosaic calls flash_fwd / flash_bwd_fused / delta_chunk_fwd / delta_chunk_bwd in the step: {kernels}",
        )
        _kv_read_by_group(text, "flash_fwd", model, c)
    _forward_kernels_run_once(got, {"flash_fwd": 1}, on_tpu)
    share = got["gained"]["moe.held_share"] / got["gained"]["moe.steps"]
    routed = cfg["steps"] * model.num_layers * c["batch"] * c["seq"] * model.experts_per_token
    _check(abs(share - got["due"] / routed) < 1e-9, f"moe.held_share reads {share}, the counts give {got['due'] / routed}")
    return dict(
        params=got["params"], losses=got["losses"], mosaic_kernels=kernels, attn_kept=got["kept"],
        held_share=round(share, 5), even_share=model.experts_held[1] / model.num_experts,
    )


def _forward_kernels_run_once(got, forward, on_tpu):
    """Every attention block of a rematerialised model keeps its core's output
    and log-sum-exp (``attn.kept`` counts the blocks), so the step holds each
    flash forward kernel once a block: the backward pass does not run it again."""
    blocks = sum(forward.values())
    _check(got["kept"] == blocks, f"attn.kept reads {got['kept']} for {blocks} attention blocks")
    if on_tpu:
        held = {k: got["kernels"].get(k, 0) for k in forward}
        _check(held == forward, f"the step holds the forward kernels {held} times, its blocks are {forward}")


def _kv_read_by_group(text, kernel, model, c):
    kv = f"tensor<{c['batch']}x{model.num_kv_heads}x{c['seq']}x{model.head_dim}xbf16>"
    _check(
        any(kv in line for line in text.splitlines() if kernel in line),
        f"{kernel} does not read K and V at {kv}: the key-value heads were repeated",
    )


def _trinity_steps(cfg, devices, on_tpu):
    """One chip's share of ``trinity_mini`` on the first chip, every block
    rematerialised, the routers' selection biases carried and moved by
    ``balance_bias_rule`` in the same program (:func:`_lm_steps`): the chip
    took the window kernels on the sliding layers and the full form on the
    others (compiled Mosaic calls of both names), and after ``steps`` steps
    every bias is a whole number of the rule's steps from 0 and at least one
    has moved."""
    import jax

    from heat_tpu.core.communication import MeshCommunication
    from heat_tpu.nn import balance_bias_rule, causal_lm_loss, trinity_mini

    c, rate = cfg["trinity"], 0.001
    model = trinity_mini(comm=MeshCommunication(devices=devices[:1]), remat=True, **c["fields"])
    got = _lm_steps("trinity", model, causal_lm_loss(model), c, cfg["steps"], rule=balance_bias_rule(rate))
    kernels = got["kernels"]
    if on_tpu:
        wanted = {"swa_fwd", "swa_bwd_fused", "flash_fwd", "flash_bwd_fused"}
        _check(wanted <= set(kernels), f"no Mosaic calls {sorted(wanted - set(kernels))} in the step: {kernels}")
        _kv_read_by_group(got["text"], "swa_fwd", model, c)
    windowed = sum(model.window_of(i) is not None for i in range(model.num_layers))
    _forward_kernels_run_once(got, {"swa_fwd": windowed, "flash_fwd": model.num_layers - windowed}, on_tpu)
    biases = np.stack([np.asarray(b) for b in jax.tree.leaves(jax.device_get(got["state"]["route_bias"]))])
    in_steps = biases / rate
    _check(
        biases.shape == (len(model.expert_layers()), model.num_experts)
        and np.abs(in_steps - np.round(in_steps)).max() < 1e-3 and 0 < np.abs(in_steps).max() <= cfg["steps"],
        f"the selection biases are not whole steps of the rule: largest {np.abs(in_steps).max()} steps",
    )
    return dict(
        params=got["params"], losses=got["losses"], mosaic_kernels=kernels, attn_kept=got["kept"],
        largest_bias_in_steps=float(np.abs(in_steps).max()),
    )


# -- array --------------------------------------------------------------------


def _moments(ht, cfg, devices, took_mosaic):
    import jax

    n = cfg["moments_rows"]
    x = ht.random.randn(n, FEATURES, dtype=ht.float32, split=0)
    _check(_on_all_devices(x.larray, devices), "split array misses a chip")
    mu = ht.mean(x, axis=0)
    took_mosaic("mean", "_moments_kernel")
    # var dispatches the program mean has just lowered and would find it in
    # jit's memory: forget it, so that var's own dispatch is lowered too
    jax.clear_caches()
    var = ht.var(x, axis=0)
    took_mosaic("var", "_moments_kernel")
    jax.block_until_ready((mu.larray, var.larray))
    xh = x.numpy()
    mu64 = xh.mean(axis=0, dtype=np.float64)
    var64 = np.zeros(FEATURES)
    for lo in range(0, n, 1 << 20):
        var64 += ((xh[lo:lo + (1 << 20)] - mu64) ** 2).sum(axis=0)
    var64 /= n
    e_mu, e_var = _err(mu.numpy(), mu64), _err(var.numpy(), var64)
    # f32 Welford carry over n/1024 row blocks of unit-variance data
    _check(e_mu <= 1e-4 and e_var <= 1e-3, f"moments off: {e_mu}, {e_var}")
    return dict(mean_err=e_mu, var_err=e_var)


def _matmul(ht, cfg, rows):
    import jax

    n = cfg["matmul_n"]
    a = (ht.random.randn(n, n, dtype=ht.float32, split=0) / np.sqrt(n)).astype(
        ht.bfloat16
    )
    b = ht.random.randn(n, n, dtype=ht.float32, split=0).astype(ht.bfloat16)
    out = ht.matmul(a, b)
    jax.block_until_ready(out.larray)
    idx = rows(n)
    ref = a.numpy()[idx].astype(np.float64) @ b.numpy().astype(np.float64)
    err = _err(out.numpy()[idx], ref)
    # operands are exact in the oracle; f32 accumulation, bf16 result
    # (relative 2^-9) — bound at 2^-7 of the largest entry
    bound = float(np.abs(ref).max()) * 2.0 ** -7
    _check(err <= bound, f"matmul off: {err} > {bound}")
    return dict(matmul_err=err, matmul_bound=bound)


def _cdist(ht, cfg, rows, one_program):
    import jax

    m, k = cfg["cdist_rows"], cfg["cdist_k"]
    sigma = 4.0
    out = {}
    # the bench shape, then a pair that no tile divides, x and y of two lengths
    for tag, (rows_x, rows_y, kk) in (
        ("", (m, m, k)), ("_ragged", cfg["cdist_ragged"]),
    ):
        x = ht.random.rand(rows_x, kk, dtype=ht.float32, split=0)
        y = ht.random.rand(rows_y, kk, dtype=ht.float32, split=0)
        dist = one_program("cdist", "_local_dist", lambda: ht.spatial.cdist(
            x, y, quadratic_expansion=True))
        kern = one_program("rbf", "_local_dist", lambda: ht.spatial.rbf(
            x, y, sigma=sigma, quadratic_expansion=True))
        jax.block_until_ready((dist.larray, kern.larray))
        idx = rows(rows_x)
        xs, yh = x.numpy()[idx].astype(np.float64), y.numpy().astype(np.float64)
        d2 = (xs * xs).sum(1)[:, None] + (yh * yh).sum(1)[None, :] - 2.0 * xs @ yh.T
        e_d = _err(np.asarray(dist.larray[idx]), np.sqrt(d2))
        e_k = _err(np.asarray(kern.larray[idx]), np.exp(-d2 / (2 * sigma * sigma)))
        # bf16x3 dot: ~2^-16 of |x||y| (~43 at k=128) on d2, halved again by
        # the square root at d ~ 4.6
        _check(e_d <= 1e-3 and e_k <= 1e-4, f"cdist{tag} off: {e_d}, rbf {e_k}")
        out.update({f"cdist_err{tag}": e_d, f"rbf_err{tag}": e_k})
    return out


def _lloyd64(x, centers, iters):
    """Float64 Lloyd iterations; an empty cluster keeps its center."""
    k = centers.shape[0]
    for _ in range(iters):
        d2 = (centers * centers).sum(1)[None, :] - 2.0 * x @ centers.T
        lab = d2.argmin(1)
        cnt = np.bincount(lab, minlength=k)
        sums = np.stack(
            [np.bincount(lab, weights=x[:, j], minlength=k)
             for j in range(x.shape[1])], axis=1,
        )
        centers = np.where(
            cnt[:, None] > 0, sums / np.maximum(cnt, 1)[:, None], centers
        )
    return centers


def _lasso64(x, y, lam, sweeps):
    """Float64 coordinate descent, intercept first, as lasso._cd_sweep."""
    n = x.shape[0]
    xb = np.concatenate([np.ones((n, 1)), x], axis=1)
    z = (xb * xb).mean(0)
    theta = np.zeros(xb.shape[1])
    for _ in range(sweeps):
        y_est = xb @ theta
        for j in range(xb.shape[1]):
            xj = xb[:, j]
            rho = (xj * (y - y_est + theta[j] * xj)).mean()
            soft = np.sign(rho) * max(abs(rho) - lam, 0.0)
            new = (rho if j == 0 else soft) / max(z[j], 1e-30)
            y_est += (new - theta[j]) * xj
            theta[j] = new
    return theta


def _kmeans_lasso(ht, cfg, took_mosaic):
    import jax

    from heat_tpu import telemetry

    n, k, iters = cfg["kmeans_rows"], cfg["kmeans_k"], cfg["iters"]
    x = ht.random.randn(n, FEATURES, dtype=ht.float32, split=0)
    xh = x.numpy()
    x64 = xh.astype(np.float64)

    km = ht.cluster.KMeans(
        n_clusters=k, init=ht.array(xh[:k]), max_iter=iters, tol=0.0
    )
    # both orientations of the Lloyd kernel carry one name; the counter
    # says which the fit took: FEATURES is no lane multiple, so X lies
    # feature-major on the chip and the blocks follow it
    # and the labels and inertia come from the kernel's own final pass
    said = ("kmeans.lloyd.feature_major", "kmeans.assign.kernel")
    counters = telemetry.get_registry().counters
    before = [counters.get(c, 0) for c in said]
    km.fit(x)
    took_mosaic("KMeans.fit", "lloyd_update", "lloyd_assign")
    _check(
        jax.default_backend() != "tpu"
        or [counters.get(c, 0) for c in said] == [b + 1 for b in before],
        f"KMeans.fit did not count {said}",
    )
    jax.block_until_ready(km.cluster_centers_.larray)
    _check(km.n_iter_ == iters, f"Lloyd ran {km.n_iter_} of {iters} iterations")
    e_c = _err(km.cluster_centers_.numpy(), _lloyd64(x64, x64[:k], iters))
    # unstructured data: rows within the bf16x3 score error of a Voronoi
    # face (~1e-4 of them) may change side; each moves a center of ~n/k
    # rows by ~|x|k/n
    _check(e_c <= 5e-3, f"KMeans centers off: {e_c}")

    w = ht.random.randn(FEATURES, 1, dtype=ht.float32)
    y = ht.matmul(x, w)
    lam = 0.01
    est = ht.regression.Lasso(lam=lam, max_iter=iters, tol=0.0)
    est.fit(x, y)
    jax.block_until_ready(est.theta.larray)
    theta64 = _lasso64(x64, y.numpy().astype(np.float64)[:, 0], lam, iters)
    e_t = _err(est.theta.numpy().ravel(), theta64)
    # each sweep restarts from theta @ x at TPU default matmul precision
    # (operands rounded to bf16, 2^-9)
    bound = 2e-2 * float(np.abs(theta64).max())
    _check(e_t <= bound, f"Lasso coefficients off: {e_t} > {bound}")
    return dict(kmeans_err=e_c, lasso_err=e_t, lasso_bound=bound)


def stage_array(ht, cfg, devices, on_tpu):
    import jax

    ht.random.seed(0)
    rng = np.random.default_rng(1)

    def rows(n):
        return np.sort(rng.choice(n, min(ORACLE_ROWS, n), replace=False))

    # JAX writes every module it hands to the compiler (or looks up in the
    # persistent cache) under jax_dump_ir_to: the lowered text of what the
    # user's call dispatched, not of what this script thinks it dispatches
    read = set()

    def lowered(call):
        """The modules lowered since the last look, text by file name."""
        new = set(os.listdir(ir_dir)) - read
        read.update(new)
        _check(new, f"{call} lowered no module")
        return {f: open(os.path.join(ir_dir, f)).read() for f in new}

    def took_mosaic(call, *kernels):
        """Among the modules lowered since the last look there is a Mosaic
        custom call named by each of ``kernels`` (the ``pallas_call``'s
        ``name=``, or its kernel function's where it gives none): the call
        took the Pallas path, compiled. (Off the TPU the library's gates
        choose the XLA forms.)"""
        new = lowered(call)
        for kernel in kernels:
            _check(
                not on_tpu or re.search(
                    rf'@tpu_custom_call\(.*kernel_name = "{kernel}"',
                    "".join(new.values()),
                ),
                f"{call}: no Mosaic call of {kernel} in the "
                f"{len(new)} modules it lowered",
            )

    def one_program(call, program, fn):
        """Run ``fn``: it lowered one module, the jitted ``program``, with
        no Mosaic call in it: one XLA program on every backend. (Beside it
        only the relayout that makes a split y whole on every chip.)"""
        read.update(os.listdir(ir_dir))
        out = fn()
        new = lowered(call)
        progs = [f for f in new if "_relayout_program_" not in f]
        _check(
            len(progs) == 1 and f"_jit_{program}_" in progs[0],
            f"{call}: lowered {sorted(new)}, not {program} alone",
        )
        _check(
            "tpu_custom_call" not in "".join(new.values()),
            f"{call}: a Mosaic call in {sorted(new)}",
        )
        return out

    out = {}
    with tempfile.TemporaryDirectory() as ir_dir:
        jax.config.update("jax_dump_ir_to", ir_dir)
        try:
            for part in (
                lambda: _moments(ht, cfg, devices, took_mosaic),
                lambda: _matmul(ht, cfg, rows),
                lambda: _cdist(ht, cfg, rows, one_program),
                lambda: _kmeans_lasso(ht, cfg, took_mosaic),
            ):
                out.update(part())
                gc.collect()  # the next workload gets the chip's memory back
        finally:
            jax.config.update(
                "jax_dump_ir_to", os.environ.get("JAX_DUMP_IR_TO", "")
            )
    return {k: float(f"{v:.3g}") for k, v in out.items()}


# -- kernels ------------------------------------------------------------------


def stage_kernels(cfg, on_tpu):
    import jax
    import jax.numpy as jnp

    from heat_tpu.cluster import kmeans as _kmeans
    from heat_tpu.cluster.pallas_lloyd import lloyd_fit_pallas
    from heat_tpu.core import program_cache
    from heat_tpu.core.linalg.quant import int8_matmul, quantize_int8
    from heat_tpu.core.pallas_moments import column_moments
    from heat_tpu.parallel import flash_attention, local_attention

    # On a TPU nobody names the interpreter: flash attention and the int8
    # GEMM choose by backend, the other kernels compile unless told
    # otherwise, and the lowering shows which side each took.
    rehearse = {} if on_tpu else {"interpret": True}
    key = jax.random.PRNGKey(2)
    report, wrong = {}, []

    def run(name, fn, ref_fn, args, bound, relative=False):
        """Lower ``fn``, require the Mosaic custom call, run it, and bound
        its distance from the XLA form (``relative``: over the largest value
        of each result). Every kernel reports; the stage fails at the end if
        any was wrong."""
        prog = program_cache.cached_program(f"smoke.{name}", (), lambda: fn)
        lowered = prog.lower(*args)
        if on_tpu and "tpu_custom_call" not in lowered.as_text():
            wrong.append(f"{name}: no Mosaic custom call in the lowering")
        got = jax.block_until_ready(lowered.compile()(*args))
        ref = jax.block_until_ready(
            program_cache.cached_program(
                f"smoke.{name}_xla", (), lambda: ref_fn
            )(*args)
        )
        err = max(
            _err(g, np.asarray(r, np.float64)) / (float(np.max(np.abs(r))) if relative else 1.0)
            for g, r in zip(jax.tree.leaves(got), jax.tree.leaves(ref))
        )
        if not (np.isfinite(err) and err <= bound):
            wrong.append(f"{name}: {err} > {bound}")
        report[name] = float(f"{err:.3g}")

    def qkv(shape):
        return tuple(
            jax.random.normal(k, shape, jnp.bfloat16)
            for k in jax.random.split(key, 3)
        )

    # bf16 attention: p and the output round to bf16 (2^-9 of O(1) values)
    run(
        "flash_fwd",
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        lambda q, k, v: local_attention(q, k, v, causal=True),
        qkv(cfg["attn_fwd"]), 3e-2,
    )

    def attn_grads(attend):
        def loss(q, k, v):
            return attend(q, k, v).astype(jnp.float32).sum()

        return jax.grad(loss, argnums=(0, 1, 2))

    for impl in ("two_pass", "fused"):
        run(
            f"flash_bwd_{impl}",
            attn_grads(lambda q, k, v, impl=impl: flash_attention(
                q, k, v, causal=True, bwd_impl=impl)),
            attn_grads(lambda q, k, v: local_attention(q, k, v, causal=True)),
            qkv(cfg["attn_bwd"]), 1e-1,
        )

    # 16 query heads on 2 key-value heads of 256, read by group in the kernels
    # and repeated for the XLA form
    b, t, h, hkv, d = cfg["attn_gqa"]
    gq, gk, gv = (
        jax.random.normal(k, (b, t, heads, d), jnp.bfloat16)
        for k, heads in zip(jax.random.split(jax.random.fold_in(key, 7), 3), (h, hkv, hkv))
    )
    repeated = lambda q, k, v: local_attention(  # noqa: E731
        q, jnp.repeat(k, h // hkv, axis=2), jnp.repeat(v, h // hkv, axis=2), causal=True)
    run("flash_gqa_fwd", lambda q, k, v: flash_attention(q, k, v, causal=True), repeated, (gq, gk, gv), 3e-2)
    for impl in ("two_pass", "fused"):
        run(
            f"flash_gqa_bwd_{impl}",
            attn_grads(lambda q, k, v, impl=impl: flash_attention(q, k, v, causal=True, bwd_impl=impl)),
            # dk and dv are sums over a group's 8 heads and 2,048 queries, delivered
            # in bfloat16: 2^-8 of values of a few tens (0.25 absolute observed on the chip)
            attn_grads(repeated), (gq, gk, gv), 2e-2, relative=True,
        )

    report.update(_flash_window(cfg, on_tpu, wrong))

    # the gated delta rule at the Qwen3-Next cell's heads and sizes (bfloat16
    # operands in its products; heads that remember 8 to 1,024 positions): the
    # form gated_delta_rule takes here (on a TPU the chunk step is the Pallas
    # kernel, a Mosaic call in the scan's body) against the recurrence a position
    # at a time in float32, and its gradients against the XLA form's
    from heat_tpu import telemetry
    from heat_tpu.nn import deltanet, gated_delta_rule

    b, t, hk, h, d = cfg["rule"]
    ks = jax.random.split(jax.random.fold_in(key, 11), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    rq = unit(jax.random.normal(ks[0], (b, t, hk, d), jnp.float32)) * d**-0.5
    rk = unit(jax.random.normal(ks[1], (b, t, hk, d), jnp.float32))
    rv = jax.random.normal(ks[2], (b, t, h, d), jnp.float32)
    rbeta = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h), jnp.float32))
    rg = jnp.broadcast_to(-1.0 / (8.0 * 128.0 ** (jnp.arange(h) / max(h - 1, 1))), (b, t, h)).astype(jnp.float32)
    weights = jax.random.normal(ks[4], (b, t, h, d), jnp.float32)  # of the outputs in the loss the gradients are of

    def recurrence(q, k, v, g, beta):
        def position(state, x):
            q, k, v, g, beta = x
            state = state * jnp.exp(g)[..., None, None]
            seen = jnp.einsum("bhde,bhd->bhe", state, k, precision="highest")
            state = state + jnp.einsum("bhd,bhe->bhde", k, (v - seen) * beta[..., None], precision="highest")
            return state, jnp.einsum("bhde,bhd->bhe", state, q, precision="highest")

        q, k = (jnp.repeat(a, h // hk, axis=2) for a in (q, k))
        xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
        return jnp.moveaxis(jax.lax.scan(position, jnp.zeros((b, h, d, d), jnp.float32), xs)[1], 0, 1)

    def xla_rule(*a):
        step = lambda *x: deltanet._xla_chunk_step(*x, dtype=jnp.bfloat16)  # noqa: E731
        return deltanet._chunked_rule(step, *a, deltanet.CHUNK)

    gradients = lambda rule: jax.grad(lambda *a: jnp.sum(rule(*a) * weights), argnums=range(5))  # noqa: E731
    rms = lambda got, ref: float(  # noqa: E731
        np.sqrt(np.mean((np.asarray(got, np.float64) - np.asarray(ref, np.float64)) ** 2) / np.mean(np.asarray(ref, np.float64) ** 2))
    )
    counters = telemetry.get_registry().counters
    before = {name: counters.get(name, 0) for name in ("gdn.rule.kernel", "gdn.rule.xla")}
    rule = lambda *a: gated_delta_rule(*a, dtype=jnp.bfloat16)  # noqa: E731
    args = (rq, rk, rv, rg, rbeta)
    programs = {
        name: program_cache.cached_program(f"smoke.{name}", (), lambda fn=fn: fn).lower(*args)
        for name, fn in (("delta_rule", rule), ("delta_rule_bwd", gradients(rule)))
    }
    took = {name: counters.get(name, 0) - n for name, n in before.items()}
    report.update(took)
    form = "gdn.rule.kernel" if on_tpu and d % 128 == 0 else "gdn.rule.xla"
    if took[form] != 2 or sum(took.values()) != 2:
        wrong.append(f"delta_rule: two traces of the rule counted {took}, not 2 of {form}")
    for name, lowered in programs.items():
        if on_tpu and "tpu_custom_call" not in lowered.as_text():
            wrong.append(f"{name}: no Mosaic custom call in the lowering")
    want = program_cache.cached_program("smoke.delta_rule_recurrence", (), lambda: recurrence)
    err = rms(programs["delta_rule"].compile()(*args), want(*args))
    if not err <= 2e-2:  # bfloat16 operands: 5e-3 observed
        wrong.append(f"delta_rule: rms {err} > 2e-2")
    report["delta_rule"] = float(f"{err:.3g}")
    want = program_cache.cached_program("smoke.delta_rule_bwd_xla", (), lambda: gradients(xla_rule))(*args)
    errs = [rms(g, w) for g, w in zip(programs["delta_rule_bwd"].compile()(*args), want)]
    # the two forms round the same bfloat16 operands: what is left is the order of
    # float32 sums and a rounding turned here and there (6.2e-4 observed on the chip
    # at these memories of up to 1,024 positions, 5e-5 at memories of a few)
    if not max(errs) <= 3e-3:
        wrong.append(f"delta_rule_bwd: rms of (q, k, v, g, beta) {errs} > 3e-3")
    report["delta_rule_bwd"] = float(f"{max(errs):.3g}")

    # separated blobs, one start in each: no row sits near a Voronoi face,
    # so the two programs assign alike and differ by f32 summation order
    n, kc, iters = cfg["lloyd_rows"], cfg["kmeans_k"], cfg["iters"]
    means = 4.0 * jax.random.normal(jax.random.fold_in(key, 3), (kc, FEATURES))
    xs = jax.random.normal(key, (n, FEATURES), jnp.float32) + jnp.tile(
        means, (n // kc, 1)
    ).astype(jnp.float32)
    tol = jnp.float32(0.0)
    run(
        "lloyd",
        lambda xs, c0: lloyd_fit_pallas(
            xs, c0, n, iters, tol, **rehearse)[0],
        lambda xs, c0: _kmeans._lloyd_fit(
            xs, jnp.ones((n,), jnp.float32), c0, iters, tol)[0],
        (xs, xs[:kc]), 1e-3,
    )
    # the final pass alone (no iteration), on a feature-major width: the
    # kernel's labels and inertia against XLA's pass on the same centres, at
    # a block multiple and at ragged rows with a tail past the last valid one.
    # A label that differs reads 1 / (kc - 1) or more. XLA's three-pass
    # product reads every x.c low by ~2^-16 of itself, the kernel's split
    # rounds its halves: with x.c ~ 1,024 against distances of ~64 here the
    # two inertias are 2.5e-4 apart on the chip (the kernel's is the nearer
    # to float64: PERF.md, Findings, PR 49)
    for name, rows, valid in (("lloyd_assign", n, n), ("lloyd_assign_ragged", n - 37, n - 42)):
        def kernel_pass(xs, c0, valid=valid):
            _, labels, inertia, _ = lloyd_fit_pallas(xs, c0, valid, 0, tol, **rehearse)
            return labels[:valid], inertia

        def xla_pass(xs, c0, valid=valid):
            w = (jnp.arange(xs.shape[0]) < valid).astype(jnp.float32)
            labels, inertia = _kmeans._lloyd_final(xs, w, c0)
            return labels[:valid], inertia

        run(name, kernel_pass, xla_pass, (xs[:rows], means.astype(jnp.float32)), 1e-3, relative=True)

    n = cfg["kernel_rows"]
    xm = jax.random.normal(key, (n, FEATURES), jnp.float32) + 3.0
    def kernel_moments(xm):
        mean, m2 = column_moments(xm, n, **rehearse)
        return mean, m2 / n

    run(
        "moments", kernel_moments,
        lambda xm: (xm.mean(0), ((xm - xm.mean(0)) ** 2).mean(0)),
        (xm,), 1e-4,  # f32 sums over n rows: mean 3, variance 1
    )

    n = cfg["int8_n"]
    qa, sa = quantize_int8(jax.random.normal(key, (n, n), jnp.float32), axis=1)
    qb, sb = quantize_int8(
        jax.random.normal(jax.random.fold_in(key, 2), (n, n), jnp.float32), axis=0
    )
    run(
        "int8_gemm",
        int8_matmul,
        lambda qa, sa, qb, sb: jax.lax.dot_general(
            qa, qb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32) * (sa * sb),
        (qa, sa, qb, sb), 1e-3,  # exact i32 products; one f32 rescale each
    )
    _check(not wrong, f"{wrong}; all kernels: {report}")
    return report


def _masked_attention(q, k, v, window, block=2048):
    """The masked XLA form in float32: ``softmax(q k^T / sqrt(D))`` under the
    mask ``t - window < j <= t``, a head and ``block`` queries at a time
    against all keys (a full score matrix under the mask, in blocks of queries)."""
    import jax
    import jax.numpy as jnp

    b, t, heads, d = q.shape
    k, v = (jnp.repeat(a, heads // a.shape[2], axis=2) for a in (k, v))
    block = block if t % block == 0 else t

    def one_head(qkv):
        qh, kh, vh = qkv

        @jax.checkpoint
        def one_block(args):
            first, qb = args
            q_pos, k_pos = first + jnp.arange(block)[:, None], jnp.arange(t)[None, :]
            mask = (k_pos <= q_pos) & (k_pos > q_pos - window)
            s = jnp.where(mask, jnp.matmul(qb, kh.T, precision="highest") / np.sqrt(d), -jnp.inf)
            return jnp.matmul(jax.nn.softmax(s, axis=-1), vh, precision="highest")

        return jax.lax.map(one_block, (jnp.arange(0, t, block), qh.reshape(t // block, block, d))).reshape(t, d)

    by_head = lambda a: a.transpose(0, 2, 1, 3).reshape(b * heads, t, d)  # noqa: E731
    o = jax.lax.map(one_head, (by_head(q), by_head(k), by_head(v)))
    return o.reshape(b, heads, t, d).transpose(0, 2, 1, 3)


def _flash_window(cfg, on_tpu, wrong):
    """The sliding-window flash kernels at the Trinity-Mini cell's shapes,
    through the model's own attention core (``nn.transformer._attend``, which
    counts what it dispatched): outputs and dq, dk, dv against the masked XLA
    form in float32 on the same bfloat16-rounded inputs, by root-mean-square
    gap. Limits: the kernels round the probabilities, dS and the output to
    bfloat16 (2^-9 each): 2.1e-3 to 2.9e-3 on the normal inputs and, on the
    edge probe, 1.96e-2 (dq, where dP - D nearly cancels on the one key that
    carries a query's mass; the same on every seed; my chip runs, PR 32, calls
    2 and 3); a mask one short or one long reads 0.5 and more on the edge
    probe, a skipped block 0.1 and more on the normal inputs: 6e-2 lies
    between."""
    import jax
    import jax.numpy as jnp

    from heat_tpu import telemetry
    from heat_tpu.core import program_cache
    from heat_tpu.nn.transformer import _attend

    t, h, hkv, d, window = cfg["attn_window"]
    keys = jax.random.split(jax.random.PRNGKey(32), 6)
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    normal = lambda key, heads: rounded(jax.random.normal(key, (1, t, heads, d), jnp.float32))  # noqa: E731
    # the edge probe: every key a vector of +-1, a query twice the sum of the keys window - 1 and window before it
    pk = jnp.where(jax.random.bernoulli(keys[3], 0.5, (1, t, hkv, d)), 1.0, -1.0).astype(jnp.float32)
    at = jnp.arange(t)
    pq = 2.0 * jnp.repeat(pk[:, (at - (window - 1)) % t] + pk[:, (at - window) % t], h // hkv, axis=2)
    sets = {
        "normal": (normal(keys[0], h), normal(keys[1], hkv), normal(keys[2], hkv)),
        "edge_probe": (pq, pk, normal(keys[4], hkv)),
    }
    weights = jax.random.normal(keys[5], (1, t, h, d), jnp.float32)

    def with_gradients(attend):
        def f(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(out * weights), out

        def run(q, k, v):
            (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads

        return run

    kernel = with_gradients(lambda q, k, v: _attend(
        q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), impl="flash", causal=True,
        comm=None, block_size=None, flash_bwd_impl="auto", window=window,
    ))
    counters = telemetry.get_registry().counters
    names = ("attn.window.kernel", "attn.window.xla", "attn.window.blocks_visited", "attn.window.blocks_live")
    before = {name: counters.get(name, 0) for name in names}
    lowered = program_cache.cached_program("smoke.flash_window", (), lambda: kernel).lower(*sets["normal"])
    took = {name: counters.get(name, 0) - n for name, n in before.items()}
    if took["attn.window.kernel"] != 1 or took["attn.window.xla"] != 0:
        wrong.append(f"flash_window: one trace of the windowed core counted {took}, not kernel 1 / xla 0")
    text = lowered.as_text()
    if on_tpu and not all(f'kernel_name = "{name}"' in text for name in ("swa_fwd", "swa_bwd_fused")):
        wrong.append("flash_window: no Mosaic calls swa_fwd / swa_bwd_fused in the lowering")
    program = lowered.compile()
    masked = program_cache.cached_program(
        "smoke.flash_window_xla", (), lambda: with_gradients(lambda q, k, v: _masked_attention(q, k, v, window))
    )
    rms = lambda got, ref: float(  # noqa: E731
        np.sqrt(np.mean((np.asarray(got, np.float64) - np.asarray(ref, np.float64)) ** 2) / np.mean(np.asarray(ref, np.float64) ** 2))
    )
    report = {
        "flash_window_blocks_visited_over_live": took["attn.window.blocks_visited"] / max(took["attn.window.blocks_live"], 1),
        **{f"flash_window.{name}": took[name] for name in names[:2]},
    }
    for name, qkv in sets.items():
        errs = [rms(g, w) for g, w in zip(program(*qkv), masked(*qkv))]
        if not max(errs) <= 6e-2:
            wrong.append(f"flash_window {name}: rms of (out, dq, dk, dv) {errs} > 6e-2")
        report[f"flash_window_{name}"] = float(f"{max(errs):.3g}")
    return report


# -- serve --------------------------------------------------------------------


def stage_serve(ht, cfg):
    from heat_tpu import telemetry
    from heat_tpu.core import program_cache

    ht.random.seed(3)
    k = cfg["serve_k"]
    km = ht.cluster.KMeans(n_clusters=k, max_iter=10, random_state=0)
    km.fit(ht.random.randn(cfg["serve_rows"], FEATURES, dtype=ht.float32, split=0))
    rng = np.random.default_rng(4)
    payloads = [
        rng.standard_normal((cfg["request_rows"], FEATURES)).astype(np.float32)
        for _ in range(cfg["requests"])
    ]
    queries = np.concatenate(payloads)
    want = np.asarray(km.predict(ht.array(queries)).numpy())

    server = ht.serve.Server(max_batch=64)
    try:
        server.register("kmeans", ht.serve.kmeans_predict(km))
        warm = server.warmup()
        before = program_cache.site_stats("serve.")["misses"]
        with telemetry.CompileWatcher() as w:
            futures = [server.submit("kmeans", p) for p in payloads]
            got = np.concatenate([np.asarray(f.result(120)) for f in futures])
        after = program_cache.site_stats("serve.")["misses"]
    finally:
        server.close()
    _check(
        w.events == 0 and after == before,
        f"compiled after warm-up: {dict(w.counts)}, misses {before}->{after}",
    )
    # the served kernel scores rows in the exact broadcast form, predict in
    # the HIGH-precision GEMM form: they may part only on a row that the
    # float64 oracle calls a tie (two nearest centers within 1e-4 relative)
    c64 = km.cluster_centers_.numpy().astype(np.float64)
    d2 = ((queries.astype(np.float64)[:, None, :] - c64[None]) ** 2).sum(-1)
    best2 = np.sort(d2, axis=1)[:, :2]
    tie = (best2[:, 1] - best2[:, 0]) <= 1e-4 * best2[:, 1]
    differ = got != want
    _check(not np.any(differ & ~tie), "served answers differ from predict")
    return dict(
        requests=len(payloads), rows=int(queries.shape[0]),
        tie_rows_differing=int(differ.sum()),
        warmup_programs=int(warm["programs"]),
        warmup_seconds=round(float(warm["seconds"]), 2),
    )


# -- driver -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny sizes on the CPU with the Pallas interpreter: checks the "
             "script, never the chip; every line is marked a rehearsal",
    )
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        print(
            f"chip_smoke: no TPU: JAX's default backend is "
            f"{devices[0].platform!r} ({devices[0].device_kind}). This "
            "script proves the chip; it does not fall back.",
            file=sys.stderr,
        )
        return 2
    if on_tpu and args.rehearse_cpu:
        print("chip_smoke: --rehearse-cpu on a TPU host", file=sys.stderr)
        return 2

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.core import program_cache

    cache_dir = program_cache.enable_persistent_cache()

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    where = dict(
        platform=devices[0].platform, device_kind=devices[0].device_kind,
        device_count=len(devices),
    )
    if args.rehearse_cpu:
        where["rehearsal"] = True
    cfg = TINY if args.rehearse_cpu else FULL
    failed = []

    def emit(stage, ok, seconds, **fields):
        print(json.dumps(dict(
            stage=stage, ok=ok, **where, seconds=round(seconds, 2), **fields
        )), flush=True)
        if not ok:
            failed.append(stage)

    t0 = time.perf_counter()
    try:
        if on_tpu:
            peaks = ht.chip_peaks(devices[0].device_kind)._asdict()
        else:
            peaks = None
        _check(
            ht.get_comm().size == len(devices),
            "the default communicator does not span every device",
        )
        emit("device", True, time.perf_counter() - t0, peaks=peaks,
             cache_dir=cache_dir, cache_entries=cache_entries())
    except Exception as e:  # noqa: BLE001 — reported, then fatal
        traceback.print_exc()
        emit("device", False, time.perf_counter() - t0, error=repr(e))
        return 1

    stages = {
        "train": lambda: stage_train(ht, cfg, devices, on_tpu),
        "array": lambda: stage_array(ht, cfg, devices, on_tpu),
        "kernels": lambda: stage_kernels(cfg, on_tpu),
        "serve": lambda: stage_serve(ht, cfg),
    }
    for name, stage in stages.items():
        t0 = time.perf_counter()
        try:
            with telemetry.CompileWatcher() as cw:
                fields = stage()
            emit(name, True, time.perf_counter() - t0,
                 compile_seconds=round(cw.seconds, 2), **fields)
        except Exception as e:  # noqa: BLE001 — every stage reports; rc below
            traceback.print_exc()
            emit(name, False, time.perf_counter() - t0, error=repr(e)[:2000])
        gc.collect()

    result = dict(
        ok=not failed,
        device=dict(
            platform=devices[0].platform, kind=devices[0].device_kind,
            count=len(devices),
        ),
    )
    if failed:
        result["failed"] = failed
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(dict(cache_dir=cache_dir, cache_entries=cache_entries())),
          flush=True)
    print(json.dumps(result), flush=True)
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
