"""Kind ``ouro_step``: one call is one optimizer step of Ouro-2.6B on a fresh
batch, built as a Heat user builds it: ``ht.nn.ouro_2_6b`` (the configuration's
sizes as its fields: the stack of ``num_hidden_layers`` blocks run
``total_ut_steps`` times on one set of weights, the exit gate),
``ht.nn.causal_lm_loss`` (the expectation of the exits' cross-entropies under
the gate's own distribution less ``loss.beta`` x its entropy),
``ht.nn.DataParallel(...).make_train_step`` over the cell's mesh, optax's AdamW
behind a clip at the global norm, every block application rematerialised,
parameters and optimizer state carried from call to call (the step donates
them). The loop around the step is ``chipbench/kinds/lm_step.py``'s (``call``,
``outputs``, ``optimizer``), used as it is. The numbers of ``correct``, one row
under the window's last call:

(a) ``losses_not_finite``: every step of the window gave a finite loss.
(b) **at the parameters the timed step holds after ``evaluation_step`` steps**
    from the seed's initial state (the window's own first calls made again
    after it, the same program on the same batches, so the state the window
    passed through: the window line says whether the losses came out the same;
    the count is the configuration's, not how many steps a machine fitted into
    the window, because the gaps are relative to logits that grow with
    training), on one seeded sequence, the timed loss's own program against the
    reference: ``logits_gap`` and ``logits_rms_gap`` (every exit's logits of the
    last positions through the one head; the worst exit), ``exit_pdf_gap`` (the
    largest absolute difference of any ``p_t(i)``), ``loss_gap`` (the whole
    loss, both terms), ``grad_norm_gap`` (the gradient's norm by parameter
    group, worst group; a block's gradient is the sum over its four uses; the
    gate is a group of its own); and against the reference **at the precision
    the configuration states** (``ouro_plain.last_exits``: bfloat16 operands,
    float32 accumulation and everything else): ``precision_gap``, the worst
    exit's root-mean-square gap of the same logits. A program of the stated
    precision rounds the same values at the same places and reads a fraction
    of its ``logits_rms_gap`` here; one a precision below (a bfloat16 stream,
    norms or accumulators) reads as far from it as from float32.
(c) ``replay_loss_gap``: **from the state those steps reached** (parameters,
    both moments and the step count, copied to the host), the timed step run
    ``replay_steps`` more times on the next batches, against the reference's own
    steps from the same state on the same batches: the worst relative gap of a
    step's loss: the timed step and the reference agree on the model where
    training has taken it, not at the seed's draw alone. (Inside a 2,000-step
    warm-up a step moves the loss by less than its rounding: the optimizer is
    (d)'s.)
(d) ``update_gap``: ``lm_step._update_gap`` (from the seed's initial state the
    program's gradients and the timed step ``update_steps`` times, the
    reference's AdamW on the same gradients, the worst leaf of the worst step).

Weights and batches come from the reference (``init_params``, ``batch``). An
item is a token.
"""

from __future__ import annotations

import contextlib
import gc
import json
from unittest import mock

import numpy as np

from chipbench.kinds import lm_step, trinity_step
from chipbench.kinds.glm_step import _refused, _wrong  # a control through the run's own comparison; the reference with keys changed
from chipbench.kinds.lm_step import Result, _delete, _host, call, items_per_call, optimizer, outputs  # noqa: F401

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size", "vocab_size",
    "num_hidden_layers", "rms_norm_eps", "rope_theta", "total_ut_steps",
)
NORMS = {"ln1": "g_1", "ln1_post": "g_2", "ln2": "g_3", "ln2_post": "g_4"}
DENSE = {"gate": "wf_g", "up": "wf_u", "down": "wf_d"}
EVALUATED = ("logits_gap", "logits_rms_gap", "precision_gap", "exit_pdf_gap", "loss_gap", "grad_norm_gap")
# the reference with one thing wrong: keys of its ``c`` (``ouro_plain``'s docstring) or of the loss's coefficients
WRONG = {
    "three_passes": {"passes_run": 3}, "ln_f_once": {"ln_f_once": True}, "last_exit_only": {"last_exit_only": True},
    "beta_zero": {"beta": 0.0}, "gate_gradient_stopped": {"stop_gate": True}, "one_use": {"one_use": True},
}
REPLAYED = ("beta_zero", "last_exit_only")  # of ``WRONG``, those a step's loss shows: the replay's own controls


def to_system(ref, c: dict) -> dict:
    """The reference's parameter tree in the layout of ``TransformerLM``
    (names and reshapes only)."""
    d, h, kv, dh = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    blocks = {}
    for i, lp in enumerate(ref["layers"]):
        blocks[f"block{i}"] = {
            **{name: {"scale": lp[g]} for name, g in NORMS.items()},
            **{name: {"kernel": lp[w]} for name, w in DENSE.items()},
            "attn": {
                "query": {"kernel": lp["wq"].reshape(d, h, dh)}, "key": {"kernel": lp["wk"].reshape(d, kv, dh)},
                "value": {"kernel": lp["wv"].reshape(d, kv, dh)}, "out": {"kernel": lp["wo"].reshape(h, dh, d)},
            },
        }
    return {"params": {
        "embed": {"embedding": ref["embed"]}, "ln_f": {"scale": ref["g_f"]}, "lm_head": {"kernel": ref["head"]},
        "exit_gate_kernel": ref["w_gate"].reshape(d, 1), "exit_gate_bias": ref["b_gate"].reshape(1), **blocks,
    }}


def from_system(tree) -> dict:
    """The inverse of :func:`to_system` (for parameters, their gradients or
    their moments)."""
    p = tree["params"]
    d = p["embed"]["embedding"].shape[1]
    layers = []
    for i in range(sum(k.startswith("block") for k in p)):
        b = p[f"block{i}"]
        a = b["attn"]
        layers.append({
            **{g: b[name]["scale"] for name, g in NORMS.items()},
            **{w: b[name]["kernel"] for name, w in DENSE.items()},
            "wq": a["query"]["kernel"].reshape(d, -1), "wk": a["key"]["kernel"].reshape(d, -1),
            "wv": a["value"]["kernel"].reshape(d, -1), "wo": a["out"]["kernel"].reshape(-1, d),
        })
    return {
        "embed": p["embed"]["embedding"], "g_f": p["ln_f"]["scale"], "head": p["lm_head"]["kernel"],
        "w_gate": p["exit_gate_kernel"].reshape(d), "b_gate": p["exit_gate_bias"].reshape(()), "layers": layers,
    }


def build_model(config, comm):
    """``ht.nn.ouro_2_6b`` with the configuration's sizes; a program without
    the model fails at this import."""
    from heat_tpu.nn import ouro_2_6b

    return ouro_2_6b(
        num_layers=config["num_hidden_layers"], comm=comm, remat=True, vocab_size=config["vocab_size"],
        d_model=config["hidden_size"], num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"], d_ff=config["intermediate_size"],
        passes=config["total_ut_steps"], max_len=config["max_position_embeddings"], norm_eps=config["rms_norm_eps"],
        rope_theta=float(config["rope_theta"]), init_std=config["init_std"], out_init_std=config["init_out_std"],
    )


class State(trinity_step.State):
    """``trinity_step.State`` around the other model: its ``grads`` (AdamW's
    moments step aside for the check's evaluation: the two do not fit one chip
    together) and ``batch`` as they are; the programs, the trees and the
    initial state are this kind's."""

    def __init__(self, config, comm, seed, reference):
        from heat_tpu.core import program_cache
        from heat_tpu.nn import DataParallel, causal_lm_loss, exit_distribution, read_exits

        import jax
        import jax.numpy as jnp

        self.config, self.comm, self.seed, self.ref = config, comm, seed, reference
        self.c = {k: config[k] for k in MODEL_KEYS}
        o = config["optimizer"]
        self.opt_ref = {**o, "coef": config["loss"]}
        self.sequences, self.length = config["sequences_per_step"], config["sequence_length"]
        if self.sequences % comm.size:
            raise ValueError("sequences_per_step must divide over the cell's chips")
        self.read = read_exits
        self.model = m = build_model(config, comm)
        opt = optimizer(o)
        self.loss_fn = causal_lm_loss(m, exit_beta=config["loss"]["beta"])
        dp = DataParallel(m, comm=comm, optimizer=opt, blocking_parameter_updates=True)
        self.step = dp.make_train_step(self.loss_fn, has_aux=True)
        key = json.dumps(self.c, sort_keys=True)
        self.opt_init = program_cache.cached_program(
            "ouro_step.opt_init", key, lambda: opt.init, comm=comm, out_shardings=comm.replicated(),
        )
        last = config["check"]["last_positions"]

        def evaluation(params, tokens):
            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(params, tokens)
            exits, gates = m.apply(params, tokens, head=False)
            # the head on the last positions alone, as the loss's loop takes its product
            logits = jnp.dot(
                exits[:, :, -last:].astype(m.dtype), params["params"]["lm_head"]["kernel"].astype(m.dtype),
                preferred_element_type=jnp.float32,
            )
            return loss, aux, grads, logits, jnp.exp(exit_distribution(gates))

        self.evaluation = program_cache.cached_program("ouro_step.evaluation", key, lambda: evaluation, comm=comm)
        self.norms = program_cache.cached_program(
            "ouro_step.norms", key, lambda: lambda grads: reference.group_norms(from_system(grads)), comm=comm,
        )
        self.cdf = reference.zipf_cdf(config["vocab_size"], config["zipf_s"])
        self.params = self.opt_state = None
        self.reset()

    def evaluate(self, params, tokens):
        loss, aux, grads, logits, pdf = self.evaluation(params, tokens)
        return loss, aux, self.norms(grads), logits, pdf

    def initial(self):
        cfg = self.config
        return self.ref.init_params(self.seed, self.c, cfg["init_std"], cfg["init_out_std"], cfg["init_gate_std"])

    def reset(self):
        """The seed's initial parameters and a fresh optimizer state, in
        place of whatever the state held."""
        import jax

        _delete((self.params, self.opt_state))
        self.params = jax.device_put(to_system(self.initial(), self.c), self.comm.replicated())
        self.opt_state = self.opt_init(self.params)


def setup(config, comm, seed, reference):
    return State(config, comm, seed, reference)


def summary(result):
    return {"loss": float(result.loss), **{k: float(result.aux[k]) for k in ("ce", "exit_entropy", "expected_pass")}}


def _evaluation_tokens(state):
    return state.batch(state.config["check"]["evaluation_batch"])[:1]


def _reference_evaluation(state, params_ref, products="float32"):
    """Loss, parts, gradient norms by group, every exit's last logits and the
    exit distribution of the reference at ``params_ref`` on the seeded sequence."""
    loss, parts, norms, logits = state.ref.evaluate(
        params_ref, _evaluation_tokens(state), state.c, state.config["loss"], state.config["check"]["last_positions"],
        products,
    )
    return loss, parts, norms, logits, parts["pdf"]


def _stated_logits(state, params_ref, products="operands"):
    """Every exit's logits of the last positions by the reference at the
    precision the configuration states (or ``products``)."""
    chk = state.config["check"]
    return _host(state.ref.last_exits(params_ref, _evaluation_tokens(state), state.c, chk["last_positions"], products)[1])


def _evaluation_gaps(state, got, want, stated, got_stated=None):
    """(b): ``got`` against ``want`` (loss, parts, group norms, logits ``(P, B,
    last, V)``, pdf ``(P, B, T)``); the logits' numbers are the worst exit's,
    ``precision_gap`` that of ``got``'s logits (or ``got_stated``) against ``stated``."""
    ref = state.ref
    g_loss, _, g_norms, g_logits, g_pdf = _host(got)
    w_loss, _, w_norms, w_logits, w_pdf = _host(want)
    exits = range(len(w_logits))
    g_stated = g_logits if got_stated is None else got_stated
    return {
        "logits_gap": max(ref.rel_gap(g_logits[t], w_logits[t]) for t in exits),
        "logits_rms_gap": max(ref.rms_gap(g_logits[t], w_logits[t]) for t in exits),
        "precision_gap": max(ref.rms_gap(g_stated[t], stated[t]) for t in exits),
        "exit_pdf_gap": float(np.max(np.abs(np.asarray(g_pdf, np.float64) - np.asarray(w_pdf, np.float64)))),
        "loss_gap": ref.rel_gap(g_loss, w_loss),
        "grad_norm_gap": max(ref.rel_gap(g_norms[g], w_norms[g]) for g in ref.GROUPS),
    }


def _reached(params, opt_state):
    """A train state in the reference's layout: parameters, AdamW's two moments
    and its step count (optax's ``ScaleByAdamState``, wherever the chain holds it)."""
    import jax

    adam = next(s for s in jax.tree.leaves(opt_state, is_leaf=lambda s: hasattr(s, "mu")) if hasattr(s, "mu"))
    return from_system(params), {"m": from_system(adam.mu), "v": from_system(adam.nu), "count": adam.count}


def _reference_steps(state, reached, first, n, products="float32"):
    """The reference's ``n`` steps from ``reached`` on batches ``first ..``:
    each step's loss."""
    import jax

    params, opt = jax.device_put(reached)
    losses = []
    for i in range(first, first + n):
        params, opt, loss, _ = state.ref.train_step(params, opt, state.batch(i), state.c, state.opt_ref, products)
        losses.append(float(loss))
    _delete((params, opt))
    return losses


def _loss_gaps(got, want):
    return max(abs(g - w) / abs(w) for g, w in zip(got, want))


@contextlib.contextmanager
def _this_kind():
    """``lm_step._update_gap`` reads ``from_system`` from its own module; here
    it gets this kind's for as long as it runs."""
    with mock.patch.object(lm_step, "from_system", from_system):
        yield


def _stepped_to(state, n):
    """The seed's initial state stepped ``n`` times by the timed step on the
    window's own batches ``0 .. n - 1``, in place of whatever the state held:
    each step's loss."""
    state.reset()
    losses = []
    for j in range(n):
        state.params, state.opt_state, loss, _ = state.step(state.params, state.opt_state, state.batch(j))
        losses.append(float(loss))
    return losses


def _evaluated_state(state):
    """Where (b) and (c) are taken: the state after ``evaluation_step`` steps.
    The steps' losses; that state on the host in the reference's layout
    (parameters, both moments, the count); what the timed loss's own program
    gives at its parameters (AdamW's moments wait on the host meanwhile: the
    evaluation's program does not fit beside them); and the losses of the
    ``replay_steps`` steps the timed step then goes on to make. The state is
    consumed."""
    import jax

    chk = state.config["check"]
    n = chk["evaluation_step"]
    losses = _stepped_to(state, n)
    waiting = _host(state.opt_state)
    _delete(state.opt_state)
    reached = _reached(_host(state.params), waiting)
    got = _host(state.evaluate(state.params, _evaluation_tokens(state)))
    state.opt_state = jax.device_put(waiting, state.comm.replicated())
    stepped = []
    for i in range(n, n + chk["replay_steps"]):
        state.params, state.opt_state, loss, _ = state.step(state.params, state.opt_state, state.batch(i))
        stepped.append(float(loss))
    _delete((state.params, state.opt_state))
    state.params = state.opt_state = None
    return losses, reached, got, stepped


def check(state, calls, last):
    """(a) to (d) of the module docstring. The state is consumed: the
    reference needs the room."""
    import jax

    chk = state.config["check"]
    row = {"losses_not_finite": float(sum(not np.isfinite(c.summary["loss"]) for c in calls))}
    last.params = None  # the window's state goes: ``reset`` drops it
    again, reached, got, stepped = _evaluated_state(state)
    gc.collect()
    params_ref = jax.device_put(reached[0])
    row.update(_evaluation_gaps(state, got, _reference_evaluation(state, params_ref), _stated_logits(state, params_ref)))
    _delete(params_ref)
    row["replay_loss_gap"] = _loss_gaps(stepped, _reference_steps(state, reached, len(again), chk["replay_steps"]))
    in_window = {c.index: c.summary["loss"] for c in calls}
    print(json.dumps({
        "reported": "window", "steps": len(calls), "loss_first_last": [calls[0].summary["loss"], calls[-1].summary["loss"]],
        "expected_pass_first_last": [calls[0].summary["expected_pass"], calls[-1].summary["expected_pass"]],
        "exit_entropy_last": calls[-1].summary["exit_entropy"], "evaluated_at_step": len(again), "replayed_losses": stepped,
        # the window's own first calls, made again to get there: the same losses, bit for bit?
        "steps_made_again_differ": sum(loss != in_window[i] for i, loss in enumerate(again) if i in in_window),
    }), flush=True)
    del reached
    with _this_kind():
        row["update_gap"] = lm_step._update_gap(state)
    return [(calls[-1].index, row)]


def evaluated_controls(state, params_ref, names=("bf16", *WRONG)):
    """(b) of each control of ``names`` against the reference itself at
    ``params_ref`` (``precision_gap``: the control's exits at the stated
    precision, or below it, against the reference's at the stated one):
    ``bf16`` (the reference a precision below the guarantee: a
    bfloat16 stream, accumulators, norms, gate) and ``WRONG``'s (three passes
    for four, ``ln_f`` outside the loop, the last exit alone, ``beta`` 0, the
    gate's gradient stopped, a shared weight's gradient from one pass only)."""
    want, stated = _host(_reference_evaluation(state, params_ref)), _stated_logits(state, params_ref)
    rows = {}
    for name in names:
        with _wrong(state, **WRONG.get(name, {})):
            got = _reference_evaluation(state, params_ref, "bf16" if name == "bf16" else "float32")
            got_stated = _stated_logits(state, params_ref, "bf16" if name == "bf16" else "operands")
        rows[name] = _evaluation_gaps(state, got, want, stated, got_stated)
        _refused(state, name, rows[name])
    return rows


def replayed_controls(state, reached, first, names=("bf16", *REPLAYED)):
    """(c) of each control of ``names`` against the reference's own steps from
    ``reached`` on batches ``first ..``: ``bf16`` (a mean over 4,095 positions
    averages rounding out: the replay is blind to it, here as in every training
    cell) and ``REPLAYED``'s (the entropy term left out, the last exit alone:
    a wrong term of the loss shows at once)."""
    n = state.config["check"]["replay_steps"]
    sound = _reference_steps(state, reached, first, n)
    gaps = {}
    for name in names:
        with _wrong(state, **WRONG.get(name, {})):
            gaps[name] = _loss_gaps(_reference_steps(state, reached, first, n, "bf16" if name == "bf16" else "float32"), sound)
        _refused(state, "replay." + name, {"replay_loss_gap": gaps[name]})
    return gaps


def control(state, i):
    """One row of several controls, each put through the run's own comparison
    (``chipbench/run.py::compare``) and printed (``control``: its name, its
    numbers, ``refused_by``), at the cell's own size and where ``check`` takes
    (b): the seed's initial state stepped ``evaluation_step`` times by the
    program. Of each evaluated number the row takes the **smallest** over
    :func:`evaluated_controls`, and of ``replay_loss_gap`` over
    :func:`replayed_controls` from the state those steps reached;
    ``update_gap``: AdamW with bfloat16 moments. Runs after ``check`` (which
    consumed the state)."""
    import jax

    n = state.config["check"]["evaluation_step"]
    _stepped_to(state, n)
    reached = _reached(_host(state.params), _host(state.opt_state))
    _delete((state.params, state.opt_state))
    state.params = state.opt_state = None
    row = {"losses_not_finite": 0.0}
    params_ref = jax.device_put(reached[0])
    rows = evaluated_controls(state, params_ref)
    _delete(params_ref)
    row.update({g: min(gaps[g] for gaps in rows.values()) for g in EVALUATED})
    row["replay_loss_gap"] = min(replayed_controls(state, reached, n).values())
    del reached
    with _this_kind():
        row["update_gap"] = lm_step._update_gap(state, control=True)
    return row
