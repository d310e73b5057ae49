"""Kind ``glm_step``: one call is one optimizer step of GLM-4.7-Flash on a fresh
batch, and one move of its routers' biases, built as a Heat user builds it:
``ht.nn.glm_4_7_flash`` (the configuration's sizes as its fields, this chip's
share of the experts, of the vocabulary and of the depth among them, the
prediction module behind the trunk), ``ht.nn.causal_lm_loss`` (both losses, the
module's at the configuration's weight), ``ht.nn.DataParallel(...).make_train_step``
over the cell's mesh with ``state_rule=ht.nn.balance_bias_rule(bias_rate)``,
optax's AdamW behind a clip at the global norm, every block rematerialised, the
module's too. The loop around the step, the numbers (a) to (d) of ``correct`` and
the way they are taken are those of ``chipbench/kinds/lm_step.py``; the state's
handling of the check's evaluation beside AdamW's moments, the biases' number
(f, ``bias_gap``) and the summary are ``chipbench/kinds/trinity_step.py``'s, the
update read leaf by leaf (``update_gap_unrouted``) ``chipbench/kinds/lfm2_step.py``'s,
used as they are. This file's own are the model, the mapping between the
reference's parameter tree and ``TransformerLM``'s, and these numbers:

(b') ``mtp_logits_gap``, ``mtp_logits_rms_gap``, ``mtp_loss_gap``: what (b) takes
of the trunk (the logits of a seeded sequence's last positions and the loss, at
the parameters the window ended with), of the prediction module: its logits at
its own last positions (``T - 1 - last .. T - 2``: it has no position ``T - 1``)
and its cross-entropy against the token two ahead. ``loss_gap`` is of the
step's whole loss, both terms at their weights.

(g) ``leak_gap``: the program's evaluation at those parameters on the seeded
sequence and on the same with the token at position ``j`` changed (``j`` inside
the last positions): what the trunk's logits before ``j`` and the module's before
``j - 1`` move by, over the logits' root mean square: 0 where nothing leaks; and
1 where the probe is dead: the trunk's at ``j`` or the module's at ``j - 1`` did
not move (a module fed the token itself). The control: the reference fed the
embedding two ahead.

The reference is given the same share (``num_experts_held`` of
``n_routed_experts`` from ``first_expert_held``; ``vocab_size`` rows) and, where
logits are compared, the routing of what it is compared with.
"""

from __future__ import annotations

import contextlib
import json
from unittest import mock

import numpy as np

from chipbench.kinds import lfm2_step, lm_step, trinity_step
from chipbench.kinds.lm_step import (  # noqa: F401  (run.py and limits.py read the kind's functions from here)
    Result, _delete, _end_of_window, _evaluation_tokens, _host, _replay, _replay_gaps, call, items_per_call,
    optimizer, outputs,
)
from chipbench.kinds.trinity_step import _bias_gap, _reference_with, biases_of, expected_biases  # noqa: F401

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "rms_norm_eps", "rope_theta", "intermediate_size", "first_k_dense_replace", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "num_experts_held", "first_expert_held", "moe_intermediate_size",
    "norm_topk_prob", "routed_scaling_factor", "vocab_size", "num_hidden_layers", "num_nextn_predict_layers",
    "bias_rate",
)
NORMS = {"ln1": "g_a", "ln2": "g_c"}
LATENT = {"q_a": "wq_a", "q_b": "wq_b", "kv_a": "wkv_a", "kv_b": "wkv_b", "out": "wo"}
LATENT_NORMS = {"q_a_norm": "g_qa", "kv_a_norm": "g_kva"}
DENSE = {"gate": "wf_g", "up": "wf_u", "down": "wf_d"}
SHARED = {"shared_gate": "ws_g", "shared_up": "ws_u", "shared_down": "ws_d"}
EXPERTS = {"router": "wr", "w_gate": "wg", "w_up": "wu", "w_down": "wd"}
ENDS = {"ln_f": "g_f", "mtp0_enorm": "g_e", "mtp0_hnorm": "g_h", "mtp0_ln_f": "g_s"}
EVALUATED = (
    "logits_gap", "logits_rms_gap", "mtp_logits_gap", "mtp_logits_rms_gap", "loss_gap", "mtp_loss_gap",
    "grad_norm_gap", "routing_disagreement",
)
# the reference with one thing wrong: keys of its ``c`` (``glm_plain``'s docstring) or of the loss's coefficients
WRONG = {
    "rope_all": {"rope_all": True}, "own_rope_key": {"own_rope_key": True}, "no_kv_norm": {"no_kv_norm": True},
    "scale_one": {"routed_scaling_factor": 1.0}, "no_mtp_loss": {"mtp": 0.0}, "mtp_own_token": {"mtp_shift": 0},
}
REPLAYED = {"no_mtp_loss": WRONG["no_mtp_loss"], "bias_left_alone": {"bias_rate": 0.0}}  # wrong from the first step on


def mixer_to_system(lp, c: dict) -> dict:
    """A mixer's leaves in ``LatentAttention``'s layout."""
    h, nope, rope, vd = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    shapes = {"wq_b": (-1, h, nope + rope), "wkv_b": (-1, h, nope + vd), "wo": (h, vd, -1)}
    tree = {name: {"kernel": lp[w].reshape(shapes[w]) if w in shapes else lp[w]} for name, w in LATENT.items()}
    tree.update({name: {"scale": lp[g]} for name, g in LATENT_NORMS.items()})
    return tree


def mixer_from_system(a) -> dict:
    """The inverse of :func:`mixer_to_system`."""
    flat = {"wq_b": lambda k: k.reshape(k.shape[0], -1), "wkv_b": lambda k: k.reshape(k.shape[0], -1),
            "wo": lambda k: k.reshape(-1, k.shape[-1])}
    lp = {w: flat.get(w, lambda k: k)(a[name]["kernel"]) for name, w in LATENT.items()}
    lp.update({g: a[name]["scale"] for name, g in LATENT_NORMS.items()})
    return lp


def to_system(ref, c: dict) -> dict:
    """The reference's parameter tree in the layout of ``TransformerLM``
    (names and reshapes only): ``params`` (the module's block is block
    ``num_hidden_layers``) and, from ``bias``, the collection ``route_bias``."""
    blocks, biases = {}, {}
    for i, lp in enumerate(ref["layers"]):
        block = {name: {"scale": lp[g]} for name, g in NORMS.items()}
        block["attn"] = mixer_to_system(lp, c)
        if "wr" in lp:
            block["moe"] = {
                **{name: lp[w] for name, w in EXPERTS.items()},
                **{name: {"kernel": lp[w]} for name, w in SHARED.items()},
            }
            if "bias" in ref:
                biases[f"block{i}"] = {"moe": {"bias": ref["bias"][len(biases)]}}
        else:
            block.update({name: {"kernel": lp[w]} for name, w in DENSE.items()})
        blocks[f"block{i}"] = block
    tree = {"params": {
        "embed": {"embedding": ref["embed"]}, "lm_head": {"kernel": ref["head"]},
        "mtp0_eh_proj": {"kernel": ref["w_eh"]}, **{name: {"scale": ref[g]} for name, g in ENDS.items()}, **blocks,
    }}
    if biases:
        tree["route_bias"] = biases
    return tree


def from_system(tree) -> dict:
    """The inverse of :func:`to_system` (for parameters or their gradients)."""
    import jax.numpy as jnp

    p = tree["params"]
    layers = []
    for i in range(sum(k.startswith("block") for k in p)):
        b = p[f"block{i}"]
        lp = {g: b[name]["scale"] for name, g in NORMS.items()}
        lp.update(mixer_from_system(b["attn"]))
        if "moe" in b:
            lp.update({w: b["moe"][name] for name, w in EXPERTS.items()})
            lp.update({w: b["moe"][name]["kernel"] for name, w in SHARED.items()})
        else:
            lp.update({w: b[name]["kernel"] for name, w in DENSE.items()})
        layers.append(lp)
    out = {
        "embed": p["embed"]["embedding"], "head": p["lm_head"]["kernel"], "w_eh": p["mtp0_eh_proj"]["kernel"],
        **{g: p[name]["scale"] for name, g in ENDS.items()}, "layers": layers,
    }
    if "route_bias" in tree:
        out["bias"] = biases_of(tree, jnp)
    return out


def build_model(config, comm):
    """``ht.nn.glm_4_7_flash`` with the configuration's sizes; a program
    without the model fails at this import."""
    from heat_tpu.nn import Latent, glm_4_7_flash

    return glm_4_7_flash(
        num_layers=config["num_hidden_layers"],
        experts_held=(config["first_expert_held"], config["num_experts_held"]),
        vocab_size=config["vocab_size"], mtp_modules=config["num_nextn_predict_layers"], comm=comm, remat=True,
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        latent=Latent(
            config["q_lora_rank"], config["kv_lora_rank"], config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"],
        ),
        rope_theta=float(config["rope_theta"]), norm_eps=config["rms_norm_eps"],
        dense_layers=config["first_k_dense_replace"], dense_d_ff=config["intermediate_size"],
        d_ff=config["moe_intermediate_size"], num_experts=config["n_routed_experts"],
        experts_per_token=config["num_experts_per_tok"], norm_topk=config["norm_topk_prob"],
        route_scale=float(config["routed_scaling_factor"]),
        shared_d_ff=config["moe_intermediate_size"] * config["n_shared_experts"],
        max_len=config["max_position_embeddings"], held_window=float(config["held_window"]),
    )


class State(trinity_step.State):
    """``trinity_step.State`` around the other model: its ``grads`` (AdamW's
    moments step aside for the check's evaluation) and ``batch`` as they are;
    the programs, the trees and the initial state are this kind's."""

    def __init__(self, config, comm, seed, reference):
        from heat_tpu.core import program_cache
        from heat_tpu.nn import DataParallel, balance_bias_rule, causal_lm_loss, read_routing

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
        self.model = m = build_model(config, comm)
        opt = optimizer(o)
        self.loss_fn = causal_lm_loss(
            m, load_balance_coef=config["loss"]["load_balance"], router_z_coef=config["loss"]["router_z"],
            mtp_coef=config["loss"]["mtp"],
        )
        dp = DataParallel(m, comm=comm, optimizer=opt, blocking_parameter_updates=True)
        self.step = dp.make_train_step(
            self.loss_fn, has_aux=True, state_rule=balance_bias_rule(config["bias_rate"])
        )
        key = json.dumps(self.c, sort_keys=True)
        self.opt_init = program_cache.cached_program(
            "glm_step.opt_init", key, lambda: lambda tree: opt.init({"params": tree["params"]}),
            comm=comm, out_shardings=comm.replicated(),
        )
        last = config["check"]["last_positions"]

        def evaluation(params, tokens):
            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(params, tokens)
            (hidden, (ahead,)), sown = m.apply(params, tokens, head=False, mtp=True, mutable=["aux"])
            chosen = jnp.stack([sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in m.expert_layers()])
            # the head on the last positions alone, as ``lm_head`` takes its product: the trunk's last, and the
            # module's last that stand for a token (its position T - 1 does not)
            rows = jnp.stack([hidden[:, -last:], ahead[:, -last - 1:-1]])
            logits = jnp.dot(
                rows.astype(m.dtype), params["params"]["lm_head"]["kernel"].astype(m.dtype),
                preferred_element_type=jnp.float32,
            )
            return loss, aux, grads, logits, chosen

        self.evaluation = program_cache.cached_program("glm_step.evaluation", key, lambda: evaluation, comm=comm)
        self.norms = program_cache.cached_program(
            "glm_step.norms", key, lambda: lambda grads: reference.group_norms(from_system(grads)), comm=comm,
        )

        self.cdf = reference.zipf_cdf(config["vocab_size"], config["zipf_s"])
        self.params = self.opt_state = None
        self.reset()

    def evaluate(self, params, tokens):
        """Of one sequence ``tokens (1, T)``, run at the step's batch shape
        (the sequence repeated: means and shares come out the same)."""
        n = tokens.shape[1]
        loss, aux, grads, logits, chosen = self.evaluation(params, np.concatenate([tokens] * self.sequences))
        return loss, aux, self.norms(grads), logits[:, :1], chosen[:, :n]

    def initial(self):
        cfg = self.config
        return self.ref.init_params(self.seed, self.c, cfg["init_std"], cfg["init_out_std"], cfg.get("init_router_std"))

    def reset(self):
        import jax

        _delete((self.params, self.opt_state))
        self.params = jax.device_put(to_system(self.initial(), self.c), self.comm.replicated())
        self.opt_state = self.opt_init(self.params)


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def summary(result):
    out = trinity_step.summary(result)
    out["ce_mtp"] = float(result.aux["ce_mtp"])
    return out


def _evaluation_gaps(state, got, params_ref, unforced=False):
    """(b), (b') and (c): ``got`` (loss, parts, group norms, logits ``(2, 1,
    last, V)``: the trunk's and the module's, chosen) against the reference's
    at ``params_ref``, which takes the experts ``got`` chose
    (``lm_step._evaluation_gaps``, whose ``unforced`` look this kind leaves out:
    it costs a whole evaluation of the reference and is compared with nothing)."""
    ref = state.ref
    g_loss, g_parts, g_norms, g_logits, g_chosen = got
    w_loss, w_parts, w_norms, w_logits, _ = lm_step._reference_evaluation(state, params_ref, forced=g_chosen)
    probs = np.asarray(w_parts["probs"])
    k, slack = state.c["num_experts_per_tok"], state.config["check"]["routing_slack"]
    g_logits, w_logits = np.asarray(g_logits), np.asarray(w_logits)
    return {
        "logits_gap": ref.rel_gap(g_logits[0], w_logits[0]),
        "logits_rms_gap": ref.rms_gap(g_logits[0], w_logits[0]),
        "mtp_logits_gap": ref.rel_gap(g_logits[1], w_logits[1]),
        "mtp_logits_rms_gap": ref.rms_gap(g_logits[1], w_logits[1]),
        "loss_gap": ref.rel_gap(g_loss, w_loss),
        "mtp_loss_gap": ref.rel_gap(g_parts["ce_mtp"], w_parts["ce_mtp"]),
        "grad_norm_gap": max(ref.rel_gap(g_norms[g], w_norms[g]) for g in ref.GROUPS),
        "routing_disagreement": max(
            ref.routing_disagreement(np.asarray(c), p, k, slack) for c, p in zip(g_chosen, probs)
        ),
    }


@contextlib.contextmanager
def _this_kind():
    """``lm_step``'s and ``lfm2_step``'s checks read ``from_system`` and their
    helpers from their own modules; here they get this kind's for as long as
    they run."""
    with mock.patch.multiple(
        lm_step, from_system=from_system, _update_gap=lfm2_step._update_gap, _evaluation_gaps=_evaluation_gaps
    ), mock.patch.multiple(lfm2_step, from_system=from_system):
        yield


def _probe_tokens(state):
    """(g)'s two sequences, the position that differs and where it stands
    among the last positions."""
    tokens = _evaluation_tokens(state)
    last, t = state.config["check"]["last_positions"], tokens.shape[1]
    j = t - last // 2
    moved = tokens.copy()
    moved[0, j] = (tokens[0, j] + 1 + state.seed % 977) % state.c["vocab_size"]
    return tokens, moved, j - (t - last)


def _leak(before, after, cut):
    """What the trunk's logits before position ``j`` and the module's before
    ``j - 1`` (both: the first ``cut`` of the last positions) moved by, over the
    logits' root mean square; 1 where the trunk's at ``j`` or the module's at
    ``j - 1`` stood still."""
    before, after = np.asarray(before, np.float64), np.asarray(after, np.float64)
    moved = np.abs(after - before)
    rms = max(float(np.sqrt(np.mean(before**2))), 1e-30)
    live = all(moved[head, :, cut].max() > 0 for head in (0, 1))
    return max(float(moved[:, :, :cut].max()) / rms, 0.0 if live else 1.0)


def _leak_gap(state, params, control=False):
    """(g) at ``params`` (the system's tree; the control: the reference's, fed
    the embedding two ahead)."""
    tokens, moved, cut = _probe_tokens(state)
    if control:
        with _reference_with(state, mtp_shift=2):
            chk = state.config["check"]
            logits = [
                _host(state.ref.evaluate(params, x, state.c, state.config["loss"], chk["last_positions"])[3])
                for x in (tokens, moved)
            ]
    else:
        logits = [_host(state.evaluate(params, x)[3]) for x in (tokens, moved)]
    gap = _leak(*logits, cut)
    print(json.dumps({"reported": "leak_gap", "worst": gap, "cut": cut, "control": control}), flush=True)
    return gap


def check(state, calls, last):
    """(g) at the window's last parameters, then ``lm_step.check`` (a to d,
    with b') with this kind's trees, then (f)."""
    by_index = sorted(calls, key=lambda c: c.index)
    biases = _host(biases_of(last.params))  # before the check consumes the state
    _delete(state.opt_state)  # the evaluation's program does not fit beside AdamW's moments
    state.opt_state = None
    leak = _leak_gap(state, last.params)
    with _this_kind():
        rows = lm_step.check(state, calls, last)
    row = rows[-1][1]
    row["update_gap_unrouted"] = state.update_gap_unrouted
    row["leak_gap"] = leak
    counts = np.stack([c.summary["expert_counts"] for c in by_index])  # steps x expert layers x experts
    first, held = state.c["first_expert_held"], state.c["num_experts_held"]
    share = counts[:, :, first:first + held].sum(-1) / (counts.sum(-1) * held / state.c["n_routed_experts"])
    print(json.dumps({  # what the step's time follows: the rows that land here, in even shares (the module's layer last)
        "reported": "held_share", "mean": float(share.mean()), "largest_by_layer": [float(v) for v in share.max(0)],
        "steps_past_the_first_window": int((share > state.config["held_window"]).any(axis=1).sum()),
        "steps": len(by_index), "ce_mtp_first_last": [by_index[0].summary["ce_mtp"], by_index[-1].summary["ce_mtp"]],
    }), flush=True)
    if [c.index for c in by_index] == list(range(len(by_index))):  # every step of the window returned its counts
        row["bias_gap"] = _bias_gap(state, biases, list(counts))
    return rows


@contextlib.contextmanager
def _wrong(state, **changed):
    """The reference with keys of its model or of the loss's coefficients
    changed, for as long as this runs."""
    loss = {**state.config["loss"], **{k: changed.pop(k) for k in list(changed) if k in state.config["loss"]}}
    with _reference_with(state, **changed), mock.patch.dict(state.config, {"loss": loss}), mock.patch.dict(
        state.opt_ref, {"coef": loss}
    ):
        yield


def _refused(state, name, gaps):
    """One control through the run's own comparison, printed with what refused it."""
    from chipbench.run import compare

    refused = compare([(name, gaps)], state.config["limits"])
    by = sorted(g for g in gaps if not gaps[g] <= state.config["limits"][g])
    print(json.dumps({"control": name, **gaps, "refused": bool(refused), "refused_by": by}), flush=True)


def replayed_controls(state, names=("bf16", *REPLAYED)):
    """(a)'s two numbers of each control of ``names`` against the reference's
    own replay: ``bf16`` (the reference a precision below the guarantee) and
    ``REPLAYED``'s (the module's loss left out; biases left where they were:
    with best scores a few thousandths apart a bias of 0.001 moves a choice)."""
    n = state.config["check"]["replay_steps"]
    sound = _replay(state, n)
    rows = {}
    for name in names:
        with _wrong(state, **REPLAYED.get(name, {})):
            rows[name] = _replay_gaps(_replay(state, n, "bf16" if name == "bf16" else "float32"), sound)
        _refused(state, "replay." + name, rows[name])
    return rows


def evaluated_controls(state, params_ref, names=("bf16", *WRONG)):
    """(b), (b') and (c) of each control of ``names`` against the reference
    itself at ``params_ref``: ``bf16``, and ``WRONG``'s (rotary over all of a
    head, a rotary key of its own for each head, the latent norm of keys and
    values left out, the routed scale 1.0 for 1.8, the module's loss left out,
    the module fed the token itself)."""
    rows = {}
    for name in names:
        with _wrong(state, **WRONG.get(name, {})):
            got = lm_step._reference_evaluation(state, params_ref, "bf16" if name == "bf16" else "float32")
        rows[name] = _evaluation_gaps(state, got, params_ref)
        _refused(state, name, rows[name])
    return rows


def control(state, i):
    """One row, as ``lm_step.control`` gives it, of several controls, each
    owning the numbers it is meant to move, all with this kind's trees and at
    the cell's own size, each put through the run's own comparison
    (``chipbench/run.py::compare``) and printed (``control``: its name, its
    numbers, ``refused_by``). (1) :func:`replayed_controls`; the row takes
    ``replay_loss_gap`` of the module's loss left out and
    ``replay_counts_differ_share`` of the biases left alone (the precision
    control is inside both). (2) :func:`evaluated_controls` at the program's
    parameters after ``control_steps`` steps; of each evaluated number the row
    takes the **smallest** over the controls. ``update_gap``: AdamW with
    bfloat16 moments. (3) ``leak_gap``: the reference fed the embedding two
    ahead. (4) ``bias_gap``: a step that leaves the biases where they were."""
    chk = state.config["check"]
    with _this_kind():
        row = {"assignments_gap": 0.0, "losses_not_finite": 0.0}
        replays = replayed_controls(state)
        row["replay_loss_gap"] = replays["no_mtp_loss"]["replay_loss_gap"]
        row["replay_counts_differ_share"] = replays["bias_left_alone"]["replay_counts_differ_share"]
        state.reset()
        counts = []
        for j in range(chk["control_steps"]):
            state.params, state.opt_state, _, aux = state.step(state.params, state.opt_state, state.batch(j))
            counts.append(_host(aux["expert_counts"]))
        params_ref = _end_of_window(state, state.params)
        rows = evaluated_controls(state, params_ref)
        row.update({g: min(gaps[g] for gaps in rows.values()) for g in EVALUATED})
        row["leak_gap"] = _leak_gap(state, params_ref, control=True)
        _delete(state.params)
        state.params = None
        row["update_gap"] = lfm2_step._update_gap(state, control=True)
        row["update_gap_unrouted"] = state.update_gap_unrouted
    row["bias_gap"] = float(np.max(np.abs(expected_biases(state, counts))) / state.config["bias_rate"])
    return row
