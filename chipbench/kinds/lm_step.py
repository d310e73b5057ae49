"""Kind ``lm_step``: one call is one optimizer step of a causal language model
on a fresh batch, built as a Heat user builds it: ``ht.nn.olmoe_1b_7b`` (the
configuration's sizes as its fields), ``ht.nn.causal_lm_loss``,
``ht.nn.DataParallel(...).make_train_step`` over the cell's mesh, optax's
AdamW behind a clip at the global norm. Parameters and optimizer state are
carried from call to call (the step donates them); the batch is drawn on the
host from the seed and put on the device inside the call, and the loss and the
routing counts are read back in it (``ht.nn.read_routing``), as a training
loop reads its loss every step.

Weights and batches come from the reference (``init_params``, ``batch``), so
that its replay starts where the window started. Set-up warms the step up and
then puts the seed's initial state back. An item is a token.
"""

from __future__ import annotations

import gc
import json

import numpy as np

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_experts", "num_experts_per_tok",
    "intermediate_size", "vocab_size", "num_hidden_layers", "rms_norm_eps",
    "rope_theta",
)


def to_system(ref, heads: int) -> dict:
    """The reference's parameter tree in the layout of ``TransformerLM``
    (names and reshapes only)."""
    d = ref["embed"].shape[1]
    dh = d // heads
    blocks = {}
    for i, lp in enumerate(ref["layers"]):
        blocks[f"block{i}"] = {
            "ln1": {"scale": lp["g_in"]},
            "ln2": {"scale": lp["g_post"]},
            "attn": {
                "query": {"kernel": lp["wq"].reshape(d, heads, dh)},
                "key": {"kernel": lp["wk"].reshape(d, heads, dh)},
                "value": {"kernel": lp["wv"].reshape(d, heads, dh)},
                "out": {"kernel": lp["wo"].reshape(heads, dh, d)},
                "q_norm": {"scale": lp["g_q"].reshape(heads, dh)},
                "k_norm": {"scale": lp["g_k"].reshape(heads, dh)},
            },
            "moe": {"router": lp["wr"], "w_gate": lp["wg"], "w_up": lp["wu"], "w_down": lp["wd"]},
        }
    return {"params": {
        "embed": {"embedding": ref["embed"]}, "ln_f": {"scale": ref["g_f"]},
        "lm_head": {"kernel": ref["head"]}, **blocks,
    }}


def from_system(tree) -> dict:
    """The inverse of :func:`to_system` (for parameters or their gradients)."""
    p = tree["params"]
    d = p["embed"]["embedding"].shape[1]
    layers = []
    for i in range(sum(k.startswith("block") for k in p)):
        b = p[f"block{i}"]
        a, m = b["attn"], b["moe"]
        layers.append({
            "g_in": b["ln1"]["scale"], "g_post": b["ln2"]["scale"],
            "wq": a["query"]["kernel"].reshape(d, d), "wk": a["key"]["kernel"].reshape(d, d),
            "wv": a["value"]["kernel"].reshape(d, d), "wo": a["out"]["kernel"].reshape(d, d),
            "g_q": a["q_norm"]["scale"].reshape(d), "g_k": a["k_norm"]["scale"].reshape(d),
            "wr": m["router"], "wg": m["w_gate"], "wu": m["w_up"], "wd": m["w_down"],
        })
    return {"embed": p["embed"]["embedding"], "g_f": p["ln_f"]["scale"],
            "head": p["lm_head"]["kernel"], "layers": layers}


def optimizer(o: dict):
    """optax's AdamW behind a clip at the global norm, the learning rate
    rising linearly over the first ``warmup_steps`` steps (step 1 takes
    ``lr / warmup_steps``)."""
    import jax.numpy as jnp
    import optax

    def rate(count):
        return o["lr"] * jnp.minimum(1.0, (count + 1) / max(o["warmup_steps"], 1))

    return optax.chain(
        optax.clip_by_global_norm(o["clip"]),
        optax.adamw(rate, b1=o["b1"], b2=o["b2"], eps=o["eps"], weight_decay=o["weight_decay"]),
    )


def _delete(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


class Result:
    def __init__(self, loss, aux, params):
        self.loss, self.aux, self.params = loss, aux, params


class State:
    def __init__(self, config, comm, seed, reference):
        # a program without the model fails here, at once
        from heat_tpu.nn import DataParallel, causal_lm_loss, olmoe_1b_7b, read_routing

        import jax.numpy as jnp

        from heat_tpu.core import program_cache

        self.config, self.comm, self.seed, self.ref = config, comm, seed, reference
        self.c = {k: config[k] for k in MODEL_KEYS}
        o = config["optimizer"]
        self.opt_ref = {**o, "coef": config["loss"]}
        self.sequences, self.length = config["sequences_per_step"], config["sequence_length"]
        if self.sequences % comm.size:
            raise ValueError("sequences_per_step must divide over the cell's chips")
        if config["norm_topk_prob"]:
            raise ValueError("the expert layer takes the top-k weights as they are (norm_topk_prob false)")
        self.read = read_routing
        self.model = olmoe_1b_7b(
            num_layers=config["num_hidden_layers"], comm=comm,
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            num_heads=config["num_attention_heads"], d_ff=config["intermediate_size"],
            num_experts=config["num_experts"], experts_per_token=config["num_experts_per_tok"],
            max_len=config["max_position_embeddings"], norm_eps=config["rms_norm_eps"],
            rope_theta=float(config["rope_theta"]),
        )
        opt = optimizer(o)
        self.loss_fn = causal_lm_loss(
            self.model, load_balance_coef=config["loss"]["load_balance"],
            router_z_coef=config["loss"]["router_z"],
        )
        dp = DataParallel(self.model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
        self.step = dp.make_train_step(self.loss_fn, has_aux=True)
        key = tuple(sorted(self.c.items()))
        self.opt_init = program_cache.cached_program(
            "lm_step.opt_init", key, lambda: opt.init, comm=comm, out_shardings=comm.replicated(),
        )
        last = config["check"]["last_positions"]

        def evaluate(params, tokens):
            import jax

            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(params, tokens)
            logits, sown = self.model.apply(params, tokens, mutable=["aux"])
            chosen = jnp.stack([
                sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"]
                for i in range(config["num_hidden_layers"])
            ])
            return loss, aux, reference.group_norms(from_system(grads)), logits[:, -last:], chosen

        self.evaluate = program_cache.cached_program(
            "lm_step.evaluate", key, lambda: evaluate, comm=comm,
        )

        def grads(params, tokens):
            import jax

            return jax.grad(lambda p: self.loss_fn(p, tokens)[0])(params)

        self.grads = program_cache.cached_program("lm_step.grads", key, lambda: grads, comm=comm)
        self.cdf = reference.zipf_cdf(config["vocab_size"], config["zipf_s"])
        self.params = self.opt_state = None
        self.reset()

    def initial(self):
        return self.ref.init_params(self.seed, self.c, self.config["init_std"])

    def reset(self):
        """The seed's initial parameters and a fresh optimizer state, in
        place of whatever the state held."""
        import jax

        _delete((self.params, self.opt_state))
        self.params = jax.device_put(
            to_system(self.initial(), self.c["num_attention_heads"]), self.comm.replicated()
        )
        self.opt_state = self.opt_init(self.params)

    def batch(self, i):
        return self.ref.batch(self.seed, i, self.sequences, self.length, self.cdf)


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def items_per_call(config, chips):
    return config["sequences_per_step"] * config["sequence_length"]


def call(state, i):
    """Step on batch ``i``. The warm-up (``i < 0``) steps twice, the second
    time on the state the first step returned as every later call does, and
    leaves the seed's initial state behind it, so that the window's first
    step is the step the reference replays."""
    for _ in range(2 if i < 0 else 1):
        state.params, state.opt_state, loss, aux = state.step(
            state.params, state.opt_state, state.batch(i)
        )
        loss, aux = state.read(loss, aux)  # as a training loop reads its loss: every step
    if i < 0:
        import jax

        jax.block_until_ready(state.params)
        state.reset()
    return Result(loss, aux, state.params)


def outputs(result):
    """What the user holds after a step: the loss (a host number by then) and
    the new parameters."""
    import jax

    return jax.tree.leaves(result.params)


def summary(result):
    out = {k: float(result.aux[k]) for k in ("ce", "load_balance", "router_z")}
    out["loss"] = float(result.loss)
    out["expert_counts"] = np.asarray(result.aux["expert_counts"])
    out["dropped"] = int(result.aux["assignments_due"]) - int(result.aux["assignments_computed"])
    return out


def _evaluation_tokens(state):
    return state.batch(state.config["check"]["evaluation_batch"])[:1]


def _reference_evaluation(state, params_ref, products="float32", forced=None):
    """Loss, parts, gradient norms by group, last logits and chosen experts
    of the reference at ``params_ref`` on the seeded sequence; with
    ``forced``, on those experts in place of its own top-k."""
    loss, parts, norms, logits = state.ref.evaluate(
        params_ref, _evaluation_tokens(state), state.c, state.config["loss"],
        state.config["check"]["last_positions"], products,
        None if forced is None else np.asarray(forced),
    )
    return loss, parts, norms, logits, parts["chosen"]


def _evaluation_gaps(state, got, params_ref, unforced=False):
    """The numbers of (b) and (c): ``got`` (loss, parts, group norms, logits,
    chosen) against the reference's at ``params_ref``. The reference takes
    the experts ``got`` chose, so that the gaps are those of the arithmetic
    and not of a choice between two nearly tied experts; whether each choice
    was one the reference could have made is ``routing_disagreement``. With
    ``unforced`` the same gaps against the reference on its own top-k are
    printed beside them (compared with no limit: near ties decide them)."""
    ref = state.ref
    g_loss, _, g_norms, g_logits, g_chosen = got
    w_loss, w_parts, w_norms, w_logits, _ = _reference_evaluation(state, params_ref, forced=g_chosen)
    probs = np.asarray(w_parts["probs"])  # the reference's, on its own hidden states
    k, slack = state.c["num_experts_per_tok"], state.config["check"]["routing_slack"]
    if unforced:
        u_loss, _, u_norms, u_logits, u_chosen = _reference_evaluation(state, params_ref)
        print(json.dumps({
            "reported": "unforced", "logits_gap": ref.rel_gap(g_logits, u_logits),
            "logits_rms_gap": ref.rms_gap(g_logits, u_logits), "loss_gap": ref.rel_gap(g_loss, u_loss),
            "grad_norm_gap": max(ref.rel_gap(g_norms[g], u_norms[g]) for g in ref.GROUPS),
            "chosen_differ_share": float(np.mean(
                np.sort(np.asarray(g_chosen), -1) != np.sort(np.asarray(u_chosen), -1)
            )),
        }), flush=True)
    return {
        "logits_gap": ref.rel_gap(g_logits, w_logits),
        "logits_rms_gap": ref.rms_gap(g_logits, w_logits),
        "loss_gap": ref.rel_gap(g_loss, w_loss),
        "grad_norm_gap": max(ref.rel_gap(g_norms[g], w_norms[g]) for g in ref.GROUPS),
        "routing_disagreement": max(
            ref.routing_disagreement(np.asarray(c), p, k, slack) for c, p in zip(g_chosen, probs)
        ),
    }


def _update_gap(state, control=False):
    """(d) what the timed step's optimizer does to the parameters. From the
    seed's initial state, ``update_steps`` times: the program's gradients at
    the parameters it holds (``state.grads``: the step's own loss, batch and
    precision), then the timed step itself; both go to the host. Then the
    reference's own AdamW takes the same gradients from the same initial
    parameters, and per leaf the norm of (the step's parameters - its own)
    over the norm of its own update is taken; the worst leaf of the worst
    step is the number. A step that does not update reads 1 or more; a
    missing decay, a missing clip or another second moment show first on the
    norm gains, whose update is a few last bits of 1.0 (0.33, 0.087, 0.052 at
    the tests' size). Sound, it reads what the two programs' gradients differ
    by (1.5e-3 on the chip, 1e-4 on a CPU; PERF.md section 4).
    The replayed losses of (a) cannot see the optimizer: at the warm-up's
    first learning rates a step moves the loss by less than its rounding.

    ``control``: in place of the step's parameters, the reference's AdamW
    with both moments rounded to bfloat16. The state is consumed."""
    import jax

    ref = state.ref
    state.reset()
    record = []
    for n in range(state.config["check"]["update_steps"]):
        tokens = state.batch(n)
        grads = from_system(_host(state.grads(state.params, tokens)))
        state.params, state.opt_state, _, _ = state.step(state.params, state.opt_state, tokens)
        record.append((grads, None if control else from_system(_host(state.params))))
    _delete((state.params, state.opt_state))
    state.params = state.opt_state = None
    params = state.initial()
    opt = ref.adamw_init(params)
    worst, where = 0.0, ""
    for n, (grads, got) in enumerate(record):
        params, opt, gaps = ref.update_gaps(params, grads, opt, got, state.opt_ref)
        for path, gap in jax.tree_util.tree_leaves_with_path(_host(gaps)):
            if not float(gap) <= worst:  # a NaN is the worst
                worst, where = float(gap), f"step {n} {jax.tree_util.keystr(path)}"
    _delete((params, opt))
    print(json.dumps({"reported": "update_gap", "worst": worst, "at": where, "control": control}), flush=True)
    return worst


def _end_of_window(state, params):
    """Drop the optimizer state and hand ``params`` to the reference (its
    layout, the same buffers)."""
    _delete(state.opt_state)
    state.opt_state = None
    return from_system(params)


def _replay(state, n, products="float32"):
    """The reference's first ``n`` steps from the seed's initial state on the
    window's batches: per step its loss, the parts and the expert counts."""
    ref = state.ref
    params = state.initial()
    opt = ref.adamw_init(params)
    out = []
    for i in range(n):
        params, opt, loss, parts = ref.train_step(
            params, opt, state.batch(i), state.c, state.opt_ref, products
        )
        out.append({"loss": float(loss), "expert_counts": np.asarray(parts["expert_counts"])})
    _delete((params, opt))
    return out


def _replay_gaps(got, want):
    """``got``/``want``: per step ``loss`` and ``expert_counts``."""
    return {
        "replay_loss_gap": max(abs(g["loss"] - w["loss"]) / abs(w["loss"]) for g, w in zip(got, want)),
        "replay_counts_differ_share": max(
            float(np.abs(g["expert_counts"] - w["expert_counts"]).sum() / (2 * w["expert_counts"].sum()))
            for g, w in zip(got, want)
        ),
    }


def check(state, calls, last):
    """(c) every call's routing dropped nothing and its loss is finite; (b) at
    the parameters the window ended with, one seeded sequence against the
    reference; (d) the timed step's update against the reference's AdamW on
    the same gradients; (a) the window's first steps against the reference's
    replay from the seed's initial state. One row of numbers, under the last
    call's index. The state is consumed: the reference needs the room."""
    chk = state.config["check"]
    row = {
        "assignments_gap": float(max(abs(c.summary["dropped"]) for c in calls)),
        "losses_not_finite": float(sum(not np.isfinite(c.summary["loss"]) for c in calls)),
    }
    params_ref = _end_of_window(state, last.params)
    got = _host(state.evaluate(last.params, _evaluation_tokens(state)))
    row.update(_evaluation_gaps(state, got, params_ref, unforced=True))
    _delete(last.params)
    state.params = last.params = params_ref = None
    gc.collect()
    row["update_gap"] = _update_gap(state)

    by_index = {c.index: c.summary for c in calls}
    n = 0
    while n < chk["replay_steps"] and n in by_index:
        n += 1
    if n:
        row.update(_replay_gaps([by_index[i] for i in range(n)], _replay(state, n)))
    return [(calls[-1].index, row)]


def _host(tree):
    import jax

    return jax.tree.map(np.asarray, jax.device_get(tree))


def control(state, i):
    """The control's numbers: the reference a precision below the guarantee
    (``products='bf16'``) against the reference itself, on the same replay
    and on the same evaluation, at parameters like those a window ends with:
    the seed's initial state stepped ``control_steps`` times by the program.
    Runs after ``check`` (which consumed the state)."""
    chk = state.config["check"]
    n = chk["replay_steps"]
    row = {"assignments_gap": 0.0, "losses_not_finite": 0.0}
    row.update(_replay_gaps(_replay(state, n, "bf16"), _replay(state, n)))
    state.reset()
    for j in range(chk["control_steps"]):
        state.params, state.opt_state, _, _ = state.step(state.params, state.opt_state, state.batch(j))
    params_ref = _end_of_window(state, state.params)
    row.update(_evaluation_gaps(state, _reference_evaluation(state, params_ref, "bf16"), params_ref))
    _delete(state.params)
    state.params = None
    row["update_gap"] = _update_gap(state, control=True)
    return row
