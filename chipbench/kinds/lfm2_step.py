"""Kind ``lfm2_step``: one call is one optimizer step of LFM2-24B-A2B on a fresh
batch, and one move of its routers' biases, built as a Heat user builds it:
``ht.nn.lfm2_24b_a2b`` (the configuration's sizes as its fields, this chip's
share of the experts, of the vocabulary and of the depth among them),
``ht.nn.causal_lm_loss`` (the head is the embedding table),
``ht.nn.DataParallel(...).make_train_step`` over the cell's mesh with
``state_rule=ht.nn.balance_bias_rule(bias_rate)``, optax's AdamW behind a clip
at the global norm, every block rematerialised. The loop around the step, the
numbers of ``correct`` (a to d) and the way they are taken are those of
``chipbench/kinds/lm_step.py``; the state's handling of the check's evaluation
beside AdamW's moments, the biases' number (f, ``bias_gap``) and the summary are
``chipbench/kinds/trinity_step.py``'s, used as they are. This file's own are the
model, the mapping between the reference's parameter tree and
``TransformerLM``'s, the update read leaf by leaf ((d) again over the leaves
outside the expert layers, ``update_gap_unrouted``: :func:`_update_gap`), and
one number:

(g) ``conv_gap``: the program's gated short convolution (the model's own
``heat_tpu.nn.deltanet.gated_short_conv``, compiled for the chip), forward and
the gradients by B, C, x and the taps (its written-out backward pass), against
the reference's shifted sum in float32, at the cell's sequences, length, hidden
size and taps, on seeded normal inputs: the worst root-mean-square gap of the
five arrays; and its **causality**: the same program on inputs whose positions
after ``t0`` are drawn anew must give the same output at every position up to
``t0``, bit for bit: what moved there, over the output's size, counts into the
same number. The control: the reference one tap short.

The reference is given the same share (``num_experts_held`` of ``num_experts``
from ``first_expert_held``; ``vocab_size`` rows; the published blocks from
``first_block``) and, where logits are compared, the routing of what it is
compared with.
"""

from __future__ import annotations

import json
from unittest import mock

import numpy as np

from chipbench.kinds import lm_step
from chipbench.kinds import trinity_step
from chipbench.kinds.lm_step import (  # noqa: F401  (run.py and limits.py read the kind's functions from here)
    Result, _delete, _end_of_window, _host, _replay, _replay_gaps, call, items_per_call, optimizer, outputs,
)
from chipbench.kinds.trinity_step import (  # noqa: F401
    EVALUATED, _bias_gap, _reference_with, biases_of, expected_biases, summary,
)

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "norm_eps", "rope_parameters", "layer_types",
    "first_block", "conv_L_cache", "intermediate_size", "num_dense_layers", "num_experts", "num_experts_per_tok",
    "num_experts_held", "first_expert_held", "moe_intermediate_size", "norm_topk_prob", "routed_scaling_factor",
    "vocab_size", "num_hidden_layers", "bias_rate",
)
NORMS = {"ln1": "g_a", "ln2": "g_c"}
CONV = {"in_proj": "w_in", "conv": "w_conv", "out_proj": "w_out"}
DENSE = {"gate": "wf_g", "up": "wf_u", "down": "wf_d"}
EXPERTS = {"router": "wr", "w_gate": "wg", "w_up": "wu", "w_down": "wd"}


def to_system(ref, c: dict) -> dict:
    """The reference's parameter tree in the layout of ``TransformerLM``
    (names and reshapes only): ``params``, with no ``lm_head``, and, from
    ``bias``, the collection ``route_bias``."""
    d = ref["embed"].shape[1]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    blocks, biases = {}, {}
    for i, lp in enumerate(ref["layers"]):
        block = {name: {"scale": lp[g]} for name, g in NORMS.items()}
        if "w_in" in lp:
            block["conv"] = {name: lp[w] for name, w in CONV.items()}
        else:
            block["attn"] = {
                "query": {"kernel": lp["wq"].reshape(d, h, -1)}, "key": {"kernel": lp["wk"].reshape(d, kv, -1)},
                "value": {"kernel": lp["wv"].reshape(d, kv, -1)}, "out": {"kernel": lp["wo"].reshape(h, -1, d)},
                "q_norm": {"scale": lp["g_q"]}, "k_norm": {"scale": lp["g_k"]},
            }
        if "wr" in lp:
            block["moe"] = {name: lp[w] for name, w in EXPERTS.items()}
            if "bias" in ref:
                biases[f"block{i}"] = {"moe": {"bias": ref["bias"][len(biases)]}}
        else:
            block.update({name: {"kernel": lp[w]} for name, w in DENSE.items()})
        blocks[f"block{i}"] = block
    tree = {"params": {"embed": {"embedding": ref["embed"]}, "ln_f": {"scale": ref["g_f"]}, **blocks}}
    if biases:
        tree["route_bias"] = biases
    return tree


def from_system(tree) -> dict:
    """The inverse of :func:`to_system` (for parameters or their gradients)."""
    import jax.numpy as jnp

    p = tree["params"]
    d = p["embed"]["embedding"].shape[1]
    layers = []
    for i in range(sum(k.startswith("block") for k in p)):
        b = p[f"block{i}"]
        lp = {g: b[name]["scale"] for name, g in NORMS.items()}
        if "conv" in b:
            lp.update({w: b["conv"][name] for name, w in CONV.items()})
        else:
            a = b["attn"]
            lp.update({
                "wq": a["query"]["kernel"].reshape(d, -1), "wk": a["key"]["kernel"].reshape(d, -1),
                "wv": a["value"]["kernel"].reshape(d, -1), "wo": a["out"]["kernel"].reshape(-1, d),
                "g_q": a["q_norm"]["scale"], "g_k": a["k_norm"]["scale"],
            })
        if "moe" in b:
            lp.update({w: b["moe"][name] for name, w in EXPERTS.items()})
        else:
            lp.update({w: b[name]["kernel"] for name, w in DENSE.items()})
        layers.append(lp)
    out = {"embed": p["embed"]["embedding"], "g_f": p["ln_f"]["scale"], "layers": layers}
    if "route_bias" in tree:
        out["bias"] = biases_of(tree, jnp)
    return out


def build_model(config, comm):
    """``ht.nn.lfm2_24b_a2b`` with the configuration's sizes; a program
    without the model fails at this import."""
    from heat_tpu.nn import lfm2_24b_a2b

    return lfm2_24b_a2b(
        num_layers=config["num_hidden_layers"], first_block=config["first_block"],
        experts_held=(config["first_expert_held"], config["num_experts_held"]),
        vocab_size=config["vocab_size"], comm=comm, remat=True,
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["hidden_size"] // config["num_attention_heads"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]), norm_eps=config["norm_eps"],
        conv_taps=config["conv_L_cache"], dense_layers=config["num_dense_layers"], dense_d_ff=config["intermediate_size"],
        d_ff=config["moe_intermediate_size"], num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"], norm_topk=config["norm_topk_prob"],
        route_scale=float(config["routed_scaling_factor"]), max_len=config["max_position_embeddings"],
        held_window=float(config["held_window"]),
    )


class State(trinity_step.State):
    """``trinity_step.State`` around the other model: its ``grads`` (AdamW's
    moments step aside for the check's evaluation), ``evaluate`` and ``batch``
    as they are; the programs, the trees and the initial state are this kind's."""

    def __init__(self, config, comm, seed, reference):
        from heat_tpu.core import program_cache
        from heat_tpu.nn import DataParallel, balance_bias_rule, causal_lm_loss, read_routing
        from heat_tpu.nn.deltanet import gated_short_conv

        import jax
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
            self.model, load_balance_coef=config["loss"]["load_balance"], router_z_coef=config["loss"]["router_z"],
        )
        dp = DataParallel(self.model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
        self.step = dp.make_train_step(
            self.loss_fn, has_aux=True, state_rule=balance_bias_rule(config["bias_rate"])
        )
        key = json.dumps(self.c, sort_keys=True)
        self.opt_init = program_cache.cached_program(
            "lfm2_step.opt_init", key, lambda: lambda tree: opt.init({"params": tree["params"]}),
            comm=comm, out_shardings=comm.replicated(),
        )
        last = config["check"]["last_positions"]

        def evaluation(params, tokens):
            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(params, tokens)
            hidden, sown = self.model.apply(params, tokens, head=False, mutable=["aux"])
            chosen = jnp.stack([
                sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in self.model.expert_layers()
            ])
            # the tied head on the last positions alone, as the model takes its product
            logits = jnp.dot(
                hidden[:, -last:].astype(self.model.dtype),
                params["params"]["embed"]["embedding"].astype(self.model.dtype).T,
                preferred_element_type=jnp.float32,
            )
            return loss, aux, grads, logits, chosen

        self.evaluation = program_cache.cached_program("lfm2_step.evaluation", key, lambda: evaluation, comm=comm)
        self.norms = program_cache.cached_program(
            "lfm2_step.norms", key, lambda: lambda grads: reference.group_norms(from_system(grads)), comm=comm,
        )

        def mixed(b, c, x, w, weights):
            """The model's own gated convolution and its gradients."""

            def f(b, c, x, w):
                out = gated_short_conv(b, c, x, w)
                return jnp.sum(out * weights), out

            (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)(b, c, x, w)
            return (out,) + grads

        self.mixed = program_cache.cached_program("lfm2_step.mixed", key, lambda: mixed, comm=comm)
        self.cdf = reference.zipf_cdf(config["vocab_size"], config["zipf_s"])
        self.params = self.opt_state = None
        self.reset()

    def initial(self):
        return self.ref.init_params(self.seed, self.c, self.config["init_std"], self.config["init_out_std"])

    def reset(self):
        import jax

        _delete((self.params, self.opt_state))
        self.params = jax.device_put(to_system(self.initial(), self.c), self.comm.replicated())
        self.opt_state = self.opt_init(self.params)


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def _this_kind():
    """``lm_step``'s checks read ``from_system`` and ``_update_gap`` from their
    own module; here they get this one's for as long as they run."""
    return mock.patch.multiple(lm_step, from_system=from_system, _update_gap=_update_gap)


def _update_gap(state, control=False):
    """(d) as ``lm_step._update_gap`` takes it (the program's gradients, then
    the timed step, ``update_steps`` times from the seed's initial state; the
    reference's AdamW on the same gradients; per leaf the norm of what the
    step's parameters miss its own by, over the norm of its own update:
    ``update_gaps``), the worst leaf of the worst step; and the same **over the
    leaves outside the expert layers alone**, left in
    ``state.update_gap_unrouted`` for ``check``.

    Why two numbers (TPU v5e, PR 39, call 5; PERF.md section 6): the step's
    program and the check's are two compilations of one loss, and a compiler
    that may keep excess precision rounds them differently; a few in a
    thousand of a layer's top-4 choices fall the other way, more the deeper the
    layer, and the gradient of that layer's router, experts and norm gain is
    another by 5 to 10% of its size: AdamW's first steps, which move an entry
    by the learning rate in the sign of ``m / sqrt(v)``, then turn 6 to 17% of
    a router's entries (4 to 5% of them the other way), entries whose gradient
    is up to 0.7 of the leaf's root mean square: 0.27 to 0.34 on every sound
    run, none on a CPU. No rule on the gradient's size takes that out (it is
    not rounding), and the next updates see the same. The leaves whose
    gradient passes no choice of their own layer read 0.12 to 0.17, and an
    optimizer's fault (rate, moments, correction, decay, clip) is in every leaf
    alike: they carry the sharp limit, the expert layers' the one against an
    update that is missing or doubled. The line printed carries the look at
    the worst leaf (``lfm2_plain.leaf_look``): how many entries were turned,
    how many the other way, and how large their gradients are.

    ``control``: in place of the step's parameters, the reference's AdamW with
    both moments rounded to bfloat16. The state is consumed."""
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
    worst = {False: (0.0, None, None), True: (0.0, None, None)}  # over every leaf; over those outside the expert layers
    look = {}
    for n, (grads, got) in enumerate(record):
        old = _host(params)
        params, opt, gaps = ref.update_gaps(params, grads, opt, got, state.opt_ref)
        gaps = _host(gaps)
        leaves = {(i, name): gap for i, lp in enumerate(gaps["layers"]) for name, gap in lp.items()}
        leaves.update({(None, name): gap for name, gap in gaps.items() if name not in ("layers", "bias")})
        found, routed = dict(worst), ref.routed(gaps)
        for where, gap in leaves.items():
            for unrouted in (False, True) if where not in routed else (False,):
                held = found[unrouted][0]
                if held == held and not float(gap) <= held:  # a NaN is the worst, and stays
                    found[unrouted] = (float(gap), n, where)
        if got is not None and found[False] != worst[False]:  # the worst leaf so far is of this step: look at it
            (i, name), leaf = found[False][2], lambda tree: tree[name] if i is None else tree["layers"][i][name]  # noqa: E731
            look = ref.leaf_look(leaf(old), _host(leaf(params)), leaf(got), leaf(grads))
        worst = found
    _delete((params, opt))
    state.update_gap_unrouted = worst[True][0]
    at = lambda w: "" if w[1] is None else f"step {w[1]} {w[2]}"  # noqa: E731
    print(json.dumps({
        "reported": "update_gap", "worst": worst[False][0], "at": at(worst[False]), "control": control,
        "unrouted_worst": worst[True][0], "unrouted_at": at(worst[True]), **look,
    }), flush=True)
    return worst[False][0]


def _conv_inputs(state):
    """(g)'s seeded inputs: B, C, x ``(sequences, T, hidden)`` and the taps,
    the weights of the gradients' sum, and B, C, x drawn anew after ``t0``."""
    import jax
    import jax.numpy as jnp

    d, taps, t = state.c["hidden_size"], state.c["conv_L_cache"], state.length
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(state.seed % (2**31)), 7777), 8)
    normal = lambda key: jax.random.normal(key, (state.sequences, t, d), jnp.float32)  # noqa: E731
    inputs = tuple(normal(k) for k in keys[:3]) + (jax.random.uniform(keys[3], (d, taps), jnp.float32, -1.0, 1.0),)
    t0 = t // 2 + 1
    later = (jnp.arange(t) > t0)[None, :, None]
    moved = tuple(jnp.where(later, normal(k), a) for k, a in zip(keys[4:7], inputs[:3])) + inputs[3:]
    return inputs, normal(keys[7]), moved, t0


def _conv_gap(state, control=False):
    """(g): the worst rms gap of out, dB, dC, dx, dw between the program's
    gated convolution (the control: the reference one tap short) and the
    reference's, and what the program's output up to ``t0`` moves by when the
    positions after ``t0`` do, over the output's size."""
    ref = state.ref
    inputs, weights, moved, t0 = _conv_inputs(state)
    want = _host(ref.conv_and_gradients(*inputs, weights))
    if control:
        got = _host(ref.conv_and_gradients(*inputs, weights, state.c["conv_L_cache"] - 1))
        gaps = {"one_tap_short": max(ref.rms_gap(g, w) for g, w in zip(got, want))}
    else:
        got = _host(state.mixed(*inputs, weights))
        gaps = {n: ref.rms_gap(g, w) for n, g, w in zip(("out", "dB", "dC", "dx", "dw"), got, want)}
        again = _host(state.mixed(*moved, weights)[0])
        gaps["moved_before_t0"] = float(
            np.max(np.abs(again[:, :t0 + 1] - got[0][:, :t0 + 1])) / np.sqrt(np.mean(got[0] ** 2))
        )
    worst, where = 0.0, ""
    for n, gap in gaps.items():
        if not gap <= worst:  # a NaN is the worst
            worst, where = float(gap), n
    print(json.dumps({"reported": "conv_gap", "worst": worst, "at": where, "control": control}), flush=True)
    return worst


def check(state, calls, last):
    """``lm_step.check`` (a to d) with this kind's trees, then (f) and (g)."""
    by_index = sorted(calls, key=lambda c: c.index)
    biases = _host(biases_of(last.params))  # before the check consumes the state
    with _this_kind():
        rows = lm_step.check(state, calls, last)
    row = rows[-1][1]
    row["update_gap_unrouted"] = state.update_gap_unrouted
    counts = np.stack([c.summary["expert_counts"] for c in by_index])  # steps x expert layers x experts
    first, held = state.c["first_expert_held"], state.c["num_experts_held"]
    share = counts[:, :, first:first + held].sum(-1) / (counts.sum(-1) * held / state.c["num_experts"])
    print(json.dumps({  # what the step's time follows: the rows that land here, in even shares, and how often past 2
        "reported": "held_share", "mean": float(share.mean()), "largest_by_layer": [float(v) for v in share.max(0)],
        "steps_past_the_first_window": int((share > 2).any(axis=1).sum()), "steps": len(by_index),
    }), flush=True)
    if [c.index for c in by_index] == list(range(len(by_index))):  # every step of the window returned its counts
        row["bias_gap"] = _bias_gap(state, biases, list(counts))
    row["conv_gap"] = _conv_gap(state)
    return rows


def control(state, i):
    """One row, as ``lm_step.control`` gives it, of several controls, each
    owning the numbers it is meant to move, all with this kind's trees and at
    the cell's own size. (1) The reference a precision below the guarantee
    (``products='bf16'``) against the reference itself: the replay's two
    numbers, and the evaluation's (``EVALUATED``) at the program's parameters
    after ``control_steps`` steps; ``update_gap``: AdamW with bfloat16 moments.
    (2) The reference with one thing wrong against the reference itself at the
    same parameters: a convolution one tap short (``short_tap``), the table's
    gradient without the head's product (``untied_head``). Each of the three
    evaluated controls is put through the run's own comparison
    (``chipbench/run.py::compare``) and printed (``control``: its name, its row,
    ``refused_by``); of each evaluated number the row takes the **smallest** of
    the three. (3) ``conv_gap``: the reference one tap short on the probe.
    (4) ``bias_gap``: a step that leaves the biases where they were."""
    from chipbench.run import compare

    chk = state.config["check"]
    n = chk["replay_steps"]
    with _this_kind():
        row = {"assignments_gap": 0.0, "losses_not_finite": 0.0}
        row.update(_replay_gaps(_replay(state, n, "bf16"), _replay(state, n)))
        state.reset()
        counts = []
        for j in range(chk["control_steps"]):
            state.params, state.opt_state, _, aux = state.step(state.params, state.opt_state, state.batch(j))
            counts.append(_host(aux["expert_counts"]))
        params_ref = _end_of_window(state, state.params)
        rows = {"bf16": lm_step._evaluation_gaps(
            state, lm_step._reference_evaluation(state, params_ref, "bf16"), params_ref
        )}
        wrong = (("short_tap", {"conv_taps_used": state.c["conv_L_cache"] - 1}), ("untied_head", {"untied_head": True}))
        for name, changed in wrong:
            with _reference_with(state, **changed):
                got = lm_step._reference_evaluation(state, params_ref)
            rows[name] = lm_step._evaluation_gaps(state, got, params_ref)
        for name, gaps in rows.items():
            refused = compare([(name, gaps)], state.config["limits"])
            by = sorted(g for g in gaps if not gaps[g] <= state.config["limits"][g])
            print(json.dumps({"control": name, **gaps, "refused": bool(refused), "refused_by": by}), flush=True)
        row.update({g: min(gaps[g] for gaps in rows.values()) for g in EVALUATED})
        _delete(state.params)
        state.params = None
        row["update_gap"] = _update_gap(state, control=True)
        row["update_gap_unrouted"] = state.update_gap_unrouted
    row["bias_gap"] = float(np.max(np.abs(expected_biases(state, counts))) / state.config["bias_rate"])
    row["conv_gap"] = _conv_gap(state, control=True)
    return row
