"""Kind ``qnext_step``: one call is one optimizer step of Qwen3-Next on a fresh
batch, built as a Heat user builds it: ``ht.nn.qwen3_next_80b_a3b`` (the
configuration's sizes as its fields, this chip's share of the experts and of
the vocabulary among them), ``ht.nn.causal_lm_loss``,
``ht.nn.DataParallel(...).make_train_step`` over the cell's mesh, optax's AdamW
behind a clip at the global norm, every block rematerialised. The loop around
the step, the numbers of ``correct`` and the way they are taken are those of
``chipbench/kinds/lm_step.py`` (PERF.md section 4, a to d), whose functions
this file uses as they are; its own are the model, the mapping between the
reference's parameter tree and ``TransformerLM``'s, and (e), a comparison of
the delta rule alone with a control of its own.

(e) ``delta_rule_gap``: the program's chunked rule (``ht.nn.gated_delta_rule``
in the model's precision, compiled for the chip) against the reference's
recurrence in float32, at the cell's head count, head sizes and sequence
length, on seeded inputs whose heads remember 8 to 4,096 positions: the worst
head's root-mean-square gap. The configuration's own initialisation forgets
within a position or two, so that the logits of (b) cannot tell a state kept in
float32 from one kept in bfloat16; a trained model's heads remember, and this
is where the rule's precision shows. Its control is the recurrence with the
state stored in bfloat16 after every position and alpha, beta rounded to it.

The reference is given the same share (``num_experts_held`` of ``num_experts``
from ``first_expert_held``; ``vocab_size`` rows) and, where logits are compared,
the routing of what it is compared with.
"""

from __future__ import annotations

import json

import numpy as np

from chipbench.kinds import lm_step
from chipbench.kinds.lm_step import (  # noqa: F401  (run.py and limits.py read the kind's functions from here)
    Result, _delete, _end_of_window, _host, _replay, _replay_gaps, _update_gap, call, items_per_call,
    optimizer, outputs,
)

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "partial_rotary_factor",
    "rope_theta", "rms_norm_eps", "full_attention_interval", "linear_num_key_heads",
    "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim",
    "num_experts", "num_experts_per_tok", "num_experts_held", "first_expert_held", "moe_intermediate_size",
    "shared_expert_intermediate_size", "norm_topk_prob", "vocab_size", "num_hidden_layers",
)


def to_system(ref, c: dict) -> dict:
    """The reference's parameter tree in the layout of ``TransformerLM``
    (names and reshapes only)."""
    d = ref["embed"].shape[1]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    blocks = {}
    for i, lp in enumerate(ref["layers"]):
        block = {
            "ln1": {"scale": lp["g_in"]}, "ln2": {"scale": lp["g_post"]},
            "moe": {
                "router": lp["wr"], "w_gate": lp["wg"], "w_up": lp["wu"], "w_down": lp["wd"],
                "shared_gate": {"kernel": lp["ws_g"]}, "shared_up": {"kernel": lp["ws_u"]},
                "shared_down": {"kernel": lp["ws_d"]}, "shared_router": lp["ws_r"],
            },
        }
        if "wq" in lp:
            block["attn"] = {
                "query": {"kernel": lp["wq"].reshape(d, h, 2 * dh)},
                "key": {"kernel": lp["wk"].reshape(d, kv, dh)},
                "value": {"kernel": lp["wv"].reshape(d, kv, dh)},
                "out": {"kernel": lp["wo"].reshape(h, dh, d)},
                "q_norm": {"scale": lp["g_q"]}, "k_norm": {"scale": lp["g_k"]},
            }
        else:
            block["gdn"] = {
                "in_qkvz": lp["w_qkvz"], "in_ba": lp["w_ba"], "conv": lp["conv"],
                "A_log": lp["a_log"], "dt_bias": lp["dt_bias"], "norm": lp["g_o"], "out": lp["w_out"],
            }
        blocks[f"block{i}"] = block
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
        m = b["moe"]
        lp = {"g_in": b["ln1"]["scale"], "g_post": b["ln2"]["scale"]}
        if "attn" in b:
            a = b["attn"]
            lp.update({
                "wq": a["query"]["kernel"].reshape(d, -1), "wk": a["key"]["kernel"].reshape(d, -1),
                "wv": a["value"]["kernel"].reshape(d, -1), "wo": a["out"]["kernel"].reshape(-1, d),
                "g_q": a["q_norm"]["scale"], "g_k": a["k_norm"]["scale"],
            })
        else:
            g = b["gdn"]
            lp.update({
                "w_qkvz": g["in_qkvz"], "w_ba": g["in_ba"], "conv": g["conv"],
                "a_log": g["A_log"], "dt_bias": g["dt_bias"], "g_o": g["norm"], "w_out": g["out"],
            })
        lp.update({
            "wr": m["router"], "wg": m["w_gate"], "wu": m["w_up"], "wd": m["w_down"],
            "ws_g": m["shared_gate"]["kernel"], "ws_u": m["shared_up"]["kernel"],
            "ws_d": m["shared_down"]["kernel"], "ws_r": m["shared_router"],
        })
        layers.append(lp)
    return {"embed": p["embed"]["embedding"], "g_f": p["ln_f"]["scale"],
            "head": p["lm_head"]["kernel"], "layers": layers}


def build_model(config, comm):
    """``ht.nn.qwen3_next_80b_a3b`` with the configuration's sizes; a program
    without the model fails at this import."""
    from heat_tpu.nn import qwen3_next_80b_a3b

    period = config["full_attention_interval"]
    return qwen3_next_80b_a3b(
        num_layers=config["num_hidden_layers"],
        experts_held=(config["first_expert_held"], config["num_experts_held"]),
        vocab_size=config["vocab_size"], comm=comm, remat=True,
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        rotary_fraction=config["partial_rotary_factor"], rope_theta=float(config["rope_theta"]),
        norm_eps=config["rms_norm_eps"], mixers=("deltanet",) * (period - 1) + ("attention",),
        gdn_key_heads=config["linear_num_key_heads"], gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"], gdn_value_dim=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"], d_ff=config["moe_intermediate_size"],
        num_experts=config["num_experts"], experts_per_token=config["num_experts_per_tok"],
        norm_topk=config["norm_topk_prob"], shared_d_ff=config["shared_expert_intermediate_size"],
        max_len=config["max_position_embeddings"],
    )


class State(lm_step.State):
    """``lm_step.State`` around the other model: the same fields, the same
    ``reset`` and ``batch``."""

    def __init__(self, config, comm, seed, reference):
        from heat_tpu.core import program_cache
        from heat_tpu.nn import DataParallel, causal_lm_loss, read_routing

        import jax.numpy as jnp

        self.config, self.comm, self.seed, self.ref = config, comm, seed, reference
        self.c = {k: config[k] for k in MODEL_KEYS}
        o = config["optimizer"]
        self.opt_ref = {**o, "coef": config["loss"]}
        self.sequences, self.length = config["sequences_per_step"], config["sequence_length"]
        if self.sequences % comm.size:
            raise ValueError("sequences_per_step must divide over the cell's chips")
        self.read = read_routing
        self.model = build_model(config, comm)
        opt = optimizer(o)
        self.loss_fn = causal_lm_loss(
            self.model, load_balance_coef=config["loss"]["load_balance"],
            router_z_coef=config["loss"]["router_z"],
        )
        dp = DataParallel(self.model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
        self.step = dp.make_train_step(self.loss_fn, has_aux=True)
        key = tuple(sorted(self.c.items()))
        self.opt_init = program_cache.cached_program(
            "qnext_step.opt_init", key, lambda: opt.init, comm=comm, out_shardings=comm.replicated(),
        )
        last = config["check"]["last_positions"]

        def evaluation(params, tokens):
            import jax

            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(params, tokens)
            hidden, sown = self.model.apply(params, tokens, head=False, mutable=["aux"])
            chosen = jnp.stack([
                sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"]
                for i in range(config["num_hidden_layers"])
            ])
            # the head on the last positions alone, as ``lm_head`` takes its product
            logits = jnp.dot(
                hidden[:, -last:].astype(self.model.dtype),
                params["params"]["lm_head"]["kernel"].astype(self.model.dtype),
                preferred_element_type=jnp.float32,
            )
            return loss, aux, grads, logits, chosen

        # one program beside the timed step, at the step's batch shape: the check's
        # gradients (d) and its evaluation (b) both run it, so a run compiles it once
        self.evaluation = program_cache.cached_program("qnext_step.evaluation", key, lambda: evaluation, comm=comm)
        self.norms = program_cache.cached_program(
            "qnext_step.norms", key, lambda: lambda grads: reference.group_norms(from_system(grads)), comm=comm,
        )

        def rule(*inputs):
            from heat_tpu.nn import gated_delta_rule

            return gated_delta_rule(*inputs, chunk=config["delta_chunk"], dtype=self.model.dtype)

        self.rule = program_cache.cached_program("qnext_step.rule", key, lambda: rule, comm=comm)
        self.cdf = reference.zipf_cdf(config["vocab_size"], config["zipf_s"])
        self.params = self.opt_state = None
        self.reset()

    def grads(self, params, tokens):
        return self.evaluation(params, tokens)[2]

    def evaluate(self, params, tokens):
        """Of one sequence ``tokens (1, T)``, run as a batch of that sequence
        twice (the step's shape: means and shares come out the same)."""
        n = tokens.shape[1]
        loss, aux, grads, logits, chosen = self.evaluation(params, np.concatenate([tokens, tokens]))
        return loss, aux, self.norms(grads), logits[:1], chosen[:, :n]

    def initial(self):
        return self.ref.init_params(self.seed, self.c, self.config["init_std"], self.config["init_out_std"])

    def reset(self):
        import jax

        _delete((self.params, self.opt_state))
        self.params = jax.device_put(to_system(self.initial(), self.c), self.comm.replicated())
        self.opt_state = self.opt_init(self.params)


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def summary(result):
    out = lm_step.summary(result)
    out["held"] = int(result.aux["assignments_due"])
    return out


class _ThisKind:
    """``lm_step``'s checks read ``from_system`` from their own module; here
    they get this one's for as long as they run."""

    def __enter__(self):
        self.theirs = lm_step.from_system
        lm_step.from_system = from_system

    def __exit__(self, *exc):
        lm_step.from_system = self.theirs


def _rule_inputs(state):
    """Seeded inputs of the delta rule at the cell's sizes, one sequence: q
    and k of unit length (q scaled by Dk^-1/2), v normal, beta = sigmoid of a
    normal, and a decay whose memory 1 / (1 - alpha) goes from
    ``rule_memory[0]`` positions at the first head to ``rule_memory[1]`` at the
    last, geometrically, times 0.5 to 1.5 a position."""
    import jax
    import jax.numpy as jnp

    c, chk = state.c, state.config["check"]
    h, dk, dv = c["linear_num_value_heads"], c["linear_key_head_dim"], c["linear_value_head_dim"]
    t = state.length
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(state.seed % (2**31)), 7777), 5)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.float32)  # noqa: E731
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q, k = unit(normal(keys[0], (1, t, h, dk))) * dk**-0.5, unit(normal(keys[1], (1, t, h, dk)))
    v, beta = normal(keys[2], (1, t, h, dv)), jax.nn.sigmoid(normal(keys[3], (1, t, h)))
    lo, hi = chk["rule_memory"]
    memory = lo * (hi / lo) ** (jnp.arange(h, dtype=jnp.float32) / max(h - 1, 1))
    g = -(0.5 + jax.random.uniform(keys[4], (1, t, h), jnp.float32)) / memory
    return q, k, v, g, beta


def _delta_rule_gap(state, control=False):
    """(e): the worst head's rms gap between the program's chunked rule (the
    control: the recurrence with a bfloat16 state) and the float32 recurrence."""
    import jax
    import jax.numpy as jnp

    ref = state.ref
    q, k, v, g, beta = _rule_inputs(state)
    with jax.default_matmul_precision("highest"):
        recurrence = jax.jit(ref.delta_rule, static_argnums=5)
        want = _host(recurrence(q, k, v, jnp.exp(g), beta, False))
        got = _host(recurrence(q, k, v, jnp.exp(g), beta, True) if control else state.rule(q, k, v, g, beta))
    by_head = [ref.rms_gap(got[:, :, i], want[:, :, i]) for i in range(want.shape[2])]
    worst = int(np.argmax(by_head))
    print(json.dumps({"reported": "delta_rule_gap", "worst": by_head[worst], "head": worst,
                      "all_heads": ref.rms_gap(got, want), "control": control}), flush=True)
    return float(by_head[worst])


def check(state, calls, last):
    """``lm_step.check`` (a to d) with this kind's trees, and (e)."""
    with _ThisKind():
        rows = lm_step.check(state, calls, last)
    rows[-1][1]["delta_rule_gap"] = _delta_rule_gap(state)
    return rows


def control(state, i):
    """``lm_step.control`` with this kind's trees (the reference a precision
    below the guarantee in every product, norm and softmax; AdamW with bfloat16
    moments), the delta rule's own control, and, reported beside them with no
    limit, what a bfloat16 state does to the logits at the cell's own
    initialisation (``state_control``)."""
    chk = state.config["check"]
    n = chk["replay_steps"]
    with _ThisKind():
        row = {"assignments_gap": 0.0, "losses_not_finite": 0.0}
        row.update(_replay_gaps(_replay(state, n, "bf16"), _replay(state, n)))
        state.reset()
        for j in range(chk["control_steps"]):
            state.params, state.opt_state, _, _ = state.step(state.params, state.opt_state, state.batch(j))
        params_ref = _end_of_window(state, state.params)
        row.update(lm_step._evaluation_gaps(
            state, lm_step._reference_evaluation(state, params_ref, "bf16"), params_ref
        ))
        low = lm_step._reference_evaluation(state, params_ref, "bf16_state")
        want = lm_step._reference_evaluation(state, params_ref, forced=low[4])
        print(json.dumps({
            "reported": "state_control", "logits_gap": state.ref.rel_gap(low[3], want[3]),
            "logits_rms_gap": state.ref.rms_gap(low[3], want[3]),
        }), flush=True)
        _delete(state.params)
        state.params = None
        row["update_gap"] = _update_gap(state, control=True)
    row["delta_rule_gap"] = _delta_rule_gap(state, control=True)
    return row
