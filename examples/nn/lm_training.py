"""Train the flagship TransformerLM on a synthetic language task.

The reference's flagship examples (examples/nn/mnist.py, imagenet-DASO.py)
demonstrate converged training of its DP stack; this is the same
demonstration for the model family this framework adds: a causal LM with
the pluggable attention core, trained data-parallel over the mesh, with
per-epoch held-out perplexity.

Task: next-token prediction on sequences from a random 3-gram grammar —
enough structure that a 2-layer LM drives perplexity far below the
uniform-vocabulary baseline within a minute on the virtual mesh.

Run:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/nn/lm_training.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../..")))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import heat_tpu as ht
from heat_tpu.core import program_cache
from heat_tpu.nn import TransformerLM

VOCAB = 32
SEQ = 64
D_MODEL = 64
HEADS = 4
LAYERS = 2
BATCH = 32
STEPS_PER_EPOCH = 40
EPOCHS = 6


def make_corpus(n_seqs, seed):
    """Sequences from a fixed random 3-gram table: P(t | t-2, t-1)."""
    master = np.random.default_rng(7)
    # each (prev2, prev1) context strongly prefers 4 of the 32 tokens
    table = master.dirichlet(np.full(VOCAB, 0.05), size=(VOCAB, VOCAB))
    rng = np.random.default_rng(seed)
    seqs = np.zeros((n_seqs, SEQ), dtype=np.int32)
    seqs[:, :2] = rng.integers(0, VOCAB, (n_seqs, 2))
    for t in range(2, SEQ):
        p = table[seqs[:, t - 2], seqs[:, t - 1]]
        cum = p.cumsum(axis=1)
        u = rng.random((n_seqs, 1))
        seqs[:, t] = (u > cum).sum(axis=1)
    return jnp.asarray(seqs)


def main():
    comm = ht.get_comm()
    # flash = the Pallas kernel: native on TPU; on the CPU demo mesh it
    # would run under the (slow) interpreter, so use the XLA core there
    impl = "flash" if jax.default_backend() == "tpu" else "local"
    print(f"mesh: {comm.size} devices, attention core: {impl}")

    # comm= makes the flash core run on each chip's batch shard
    lm = TransformerLM(vocab_size=VOCAB, d_model=D_MODEL, num_heads=HEADS,
                       num_layers=LAYERS, max_len=SEQ, attn_impl=impl,
                       comm=comm)
    train = make_corpus(BATCH * STEPS_PER_EPOCH, seed=1)
    heldout = make_corpus(256, seed=2)

    # parameters and optimizer state replicated over the mesh from the
    # start: step 2 then sees the placement step 1 returned, and the step
    # program compiles once
    opt = optax.adamw(1e-2)
    params = jax.device_put(
        lm.init(jax.random.PRNGKey(0), train[:comm.size]), comm.replicated()
    )
    opt_state = jax.device_put(opt.init(params), comm.replicated())

    def loss_fn(p, toks):
        logits = lm.apply(p, toks[:, :-1])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, toks[:, 1:]
        ).mean()

    # dispatch through the program registry — the sanctioned jit site
    # (heatlint HL001): the demo's step/eval programs get the same cache
    # keying, HLO-audit visibility, and retrace telemetry as the framework
    def _step_fn(p, s, toks):
        l, g = jax.value_and_grad(loss_fn)(p, toks)
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s, l

    step = program_cache.cached_program(
        "example.lm_train_step", (impl, D_MODEL, LAYERS), lambda: _step_fn,
        comm=comm,
    )
    eval_loss = program_cache.cached_program(
        "example.lm_eval_loss", (impl, D_MODEL, LAYERS), lambda: loss_fn,
        comm=comm,
    )

    # batches sharded over the mesh's data axis — the DP layout
    shard = comm.sharding(0, 2)
    ppl0 = float(jnp.exp(eval_loss(params, jax.device_put(heldout, shard))))
    print(f"initial held-out perplexity {ppl0:.1f} (uniform = {VOCAB})")

    for epoch in range(EPOCHS):
        for i in range(STEPS_PER_EPOCH):
            batch = jax.device_put(train[i * BATCH:(i + 1) * BATCH], shard)
            params, opt_state, l = step(params, opt_state, batch)
        ppl = float(jnp.exp(eval_loss(params, jax.device_put(heldout, shard))))
        print(f"epoch {epoch}: train loss {float(l):.3f}, held-out perplexity {ppl:.2f}")

    assert ppl < ppl0 / 2, "LM failed to learn the 3-gram structure"
    print("converged: perplexity", round(ppl, 2), "vs uniform", VOCAB)


if __name__ == "__main__":
    main()
