"""Kind ``trinity_step``: one call is one optimizer step of Trinity-Mini on a
fresh batch, and one move of its routers' biases, built as a Heat user builds
it: ``ht.nn.trinity_mini`` (the configuration's sizes as its fields, this
chip's share of the experts and of the vocabulary among them),
``ht.nn.causal_lm_loss``, ``ht.nn.DataParallel(...).make_train_step`` over the
cell's mesh with ``state_rule=ht.nn.balance_bias_rule(bias_rate)``, optax's
AdamW behind a clip at the global norm, every block rematerialised. The loop
around the step, the numbers of ``correct`` and the way they are taken are
those of ``chipbench/kinds/lm_step.py`` (PERF.md section 4, a to d), whose
functions this file uses as they are; its own are the model, the mapping
between the reference's parameter tree and ``TransformerLM``'s, and two numbers:

(e) ``window_gap``: the program's windowed attention (the model's own
attention core, ``attn_impl`` and precision, compiled for the chip), forward
and the gradients by q, k and v, against the reference's masked form in
float32, at the cell's head counts, head size, window and sequence length, on
two seeded sets of inputs: normal q, k, v (scores of deviation 1, as the model's
own behind its head norms: a block the grid skipped would show), and the
**edge probe** (``trinity_plain.edge_probe``): the keys exactly ``window - 1``
and ``window`` before each query carry the largest scores, so that a mask one
short or one long moves the output by half or all of itself. The worst
root-mean-square gap of the eight arrays. The step's own q, k, v cannot do
this: at the cell's initialisation a query spreads over its 2,048 keys, one key
more or less moves an output by 1/2,048 of itself, far under bfloat16's
rounding, so the probe takes their place (ISSUE 32, section 3).

(f) ``bias_gap``: the biases that the timed path holds at the window's end
against the rule applied, on the host, to the expert counts that each of the
window's calls returned, in units of one update (``bias_rate``): 0 where the
step moved every bias as the rule says, 1 or more where one update is missing,
doubled or turned round.

The reference is given the same share (``num_experts_held`` of ``num_experts``
from ``first_expert_held``; ``vocab_size`` rows) and, where logits are compared,
the routing of what it is compared with.
"""

from __future__ import annotations

import contextlib
import json
import math

import numpy as np

from chipbench.kinds import lm_step
from chipbench.kinds.lm_step import (  # noqa: F401  (run.py and limits.py read the kind's functions from here)
    Result, _delete, _end_of_window, _host, _replay, _replay_gaps, _update_gap, call, items_per_call,
    optimizer, outputs,
)

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
    "global_attn_every_n_layers", "sliding_window", "intermediate_size", "num_dense_layers", "num_experts",
    "num_experts_per_tok", "num_experts_held", "first_expert_held", "moe_intermediate_size", "route_norm",
    "route_scale", "vocab_size", "num_hidden_layers", "bias_rate",
)
NORMS = {"ln1": "g_a", "ln1_post": "g_b", "ln2": "g_c", "ln2_post": "g_d"}
DENSE = {"gate": "wf_g", "up": "wf_u", "down": "wf_d"}
SHARED = {"shared_gate": "ws_g", "shared_up": "ws_u", "shared_down": "ws_d"}
EXPERTS = {"router": "wr", "w_gate": "wg", "w_up": "wu", "w_down": "wd"}


def to_system(ref, c: dict) -> dict:
    """The reference's parameter tree in the layout of ``TransformerLM``
    (names and reshapes only): ``params`` and, from ``bias``, the collection
    ``route_bias``."""
    d = ref["embed"].shape[1]
    h, kv, dh = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    blocks, biases = {}, {}
    for i, lp in enumerate(ref["layers"]):
        block = {name: {"scale": lp[g]} for name, g in NORMS.items()}
        block["attn"] = {
            "query": {"kernel": lp["wq"].reshape(d, h, 2 * dh)},
            "key": {"kernel": lp["wk"].reshape(d, kv, dh)},
            "value": {"kernel": lp["wv"].reshape(d, kv, dh)},
            "out": {"kernel": lp["wo"].reshape(h, dh, d)},
            "q_norm": {"scale": lp["g_q"]}, "k_norm": {"scale": lp["g_k"]},
        }
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
        "embed": {"embedding": ref["embed"]}, "ln_f": {"scale": ref["g_f"]},
        "lm_head": {"kernel": ref["head"]}, **blocks,
    }}
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
        a = b["attn"]
        lp = {g: b[name]["scale"] for name, g in NORMS.items()}
        lp.update({
            "wq": a["query"]["kernel"].reshape(d, -1), "wk": a["key"]["kernel"].reshape(d, -1),
            "wv": a["value"]["kernel"].reshape(d, -1), "wo": a["out"]["kernel"].reshape(-1, d),
            "g_q": a["q_norm"]["scale"], "g_k": a["k_norm"]["scale"],
        })
        if "moe" in b:
            lp.update({w: b["moe"][name] for name, w in EXPERTS.items()})
            lp.update({w: b["moe"][name]["kernel"] for name, w in SHARED.items()})
        else:
            lp.update({w: b[name]["kernel"] for name, w in DENSE.items()})
        layers.append(lp)
    out = {"embed": p["embed"]["embedding"], "g_f": p["ln_f"]["scale"],
           "head": p["lm_head"]["kernel"], "layers": layers}
    if "route_bias" in tree:
        out["bias"] = biases_of(tree, jnp)
    return out


def biases_of(tree, xp=np):
    """The selection biases of a system tree, expert layers x experts."""
    held = tree["route_bias"]
    return xp.stack([held[name]["moe"]["bias"] for name in sorted(held, key=lambda n: int(n[len("block"):]))])


def build_model(config, comm):
    """``ht.nn.trinity_mini`` with the configuration's sizes; a program
    without the model fails at this import."""
    from heat_tpu.nn import trinity_mini

    period = config["global_attn_every_n_layers"]
    return trinity_mini(
        num_layers=config["num_hidden_layers"],
        experts_held=(config["first_expert_held"], config["num_experts_held"]),
        vocab_size=config["vocab_size"], comm=comm, remat=True,
        d_model=config["hidden_size"], embed_scale=math.sqrt(config["hidden_size"]),
        num_heads=config["num_attention_heads"], num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]), norm_eps=config["rms_norm_eps"],
        windows=(config["sliding_window"],) * (period - 1) + (None,), rotary=(True,) * (period - 1) + (False,),
        dense_layers=config["num_dense_layers"], dense_d_ff=config["intermediate_size"],
        d_ff=config["moe_intermediate_size"], num_experts=config["num_experts"],
        experts_per_token=config["num_experts_per_tok"], norm_topk=config["route_norm"],
        route_scale=config["route_scale"],
        shared_d_ff=config["moe_intermediate_size"] * config["num_shared_experts"],
        max_len=config["max_position_embeddings"],
    )


class State(lm_step.State):
    """``lm_step.State`` around the other model: the same fields, the same
    ``batch``; the step also carries the biases and moves them."""

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
        self.model = build_model(config, comm)
        opt = optimizer(o)
        self.loss_fn = causal_lm_loss(
            self.model, load_balance_coef=config["loss"]["load_balance"],
            router_z_coef=config["loss"]["router_z"],
        )
        dp = DataParallel(self.model, comm=comm, optimizer=opt, blocking_parameter_updates=True)
        self.step = dp.make_train_step(
            self.loss_fn, has_aux=True, state_rule=balance_bias_rule(config["bias_rate"])
        )
        key = tuple(sorted(self.c.items()))
        self.opt_init = program_cache.cached_program(
            "trinity_step.opt_init", key, lambda: lambda tree: opt.init({"params": tree["params"]}),
            comm=comm, out_shardings=comm.replicated(),
        )
        last = config["check"]["last_positions"]

        def evaluation(params, tokens):
            (loss, aux), grads = jax.value_and_grad(self.loss_fn, has_aux=True)(params, tokens)
            hidden, sown = self.model.apply(params, tokens, head=False, mutable=["aux"])
            chosen = jnp.stack([
                sown["aux"][f"block{i}"]["moe"]["moe"][0]["chosen"] for i in self.model.expert_layers()
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
        self.evaluation = program_cache.cached_program("trinity_step.evaluation", key, lambda: evaluation, comm=comm)
        self.norms = program_cache.cached_program(
            "trinity_step.norms", key, lambda: lambda grads: reference.group_norms(from_system(grads)), comm=comm,
        )

        def windowed(q, k, v, weights):
            """The model's own windowed attention core and its gradients."""
            from heat_tpu.nn.transformer import _attend

            m = self.model

            def f(q, k, v):
                out = _attend(
                    q.astype(m.dtype), k.astype(m.dtype), v.astype(m.dtype), impl=m.attn_impl, causal=True,
                    comm=None, block_size=m.block_size, flash_bwd_impl=m.flash_bwd_impl,
                    window=config["sliding_window"],
                ).astype(jnp.float32)
                return jnp.sum(out * weights), out

            (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads

        self.windowed = program_cache.cached_program("trinity_step.windowed", key, lambda: windowed, comm=comm)
        self.cdf = reference.zipf_cdf(config["vocab_size"], config["zipf_s"])
        self.params = self.opt_state = None
        self.reset()

    def grads(self, params, tokens):
        """The program's gradients, on the host. The evaluation's program (11.0
        GiB compiled for the chip) and AdamW's two moments (5.5 GiB) do not fit
        one chip together: where the state still holds the moments they make
        room meanwhile and are there again before the step that needs them:
        moments that no step has touched yet (every count 0) are dropped and
        made anew, the others wait on the host (11.8 GB there and back)."""
        import jax
        import jax.numpy as jnp

        waiting = fresh = None
        if self.opt_state is not None:
            counts = [a for a in jax.tree.leaves(self.opt_state) if a.ndim == 0 and jnp.issubdtype(a.dtype, jnp.integer)]
            fresh = bool(counts) and not any(int(a) for a in counts)
            waiting = None if fresh else _host(self.opt_state)
            _delete(self.opt_state)
            self.opt_state = None
        grads = _host(self.evaluation(params, tokens)[2])
        if fresh:
            self.opt_state = self.opt_init(params)
        elif waiting is not None:
            self.opt_state = jax.device_put(waiting, self.comm.replicated())
        return grads

    def evaluate(self, params, tokens):
        """Of one sequence ``tokens (1, T)``, run at the step's batch shape
        (the sequence repeated: means and shares come out the same)."""
        n = tokens.shape[1]
        loss, aux, grads, logits, chosen = self.evaluation(params, np.concatenate([tokens] * self.sequences))
        return loss, aux, self.norms(grads), logits[:1], chosen[:, :n]

    def initial(self):
        return self.ref.init_params(
            self.seed, self.c, self.config["init_std"], self.config["init_out_std"], self.config["init_post_norm_gain"]
        )

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
    out["route_bias_max_abs"] = float(result.aux["route_bias_max_abs"])
    return out


class _ThisKind:
    """``lm_step``'s checks read ``from_system`` from their own module; here
    they get this one's for as long as they run."""

    def __enter__(self):
        self.theirs = lm_step.from_system
        lm_step.from_system = from_system

    def __exit__(self, *exc):
        lm_step.from_system = self.theirs


def _window_inputs(state):
    """The two seeded sets of inputs of (e), rounded to the program's operand
    precision (what the rounding of the inputs alone does is not the
    attention's gap), and the weights of the gradients' sum."""
    import jax
    import jax.numpy as jnp

    c = state.c
    h, kv, dh, t = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], state.length
    keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(state.seed % (2**31)), 8888), 4)
    normal = lambda key, heads: jax.random.normal(key, (1, t, heads, dh), jnp.float32)  # noqa: E731
    rounded = lambda a: a.astype(state.model.dtype).astype(jnp.float32)  # noqa: E731
    sets = {
        "normal": (normal(keys[0], h), normal(keys[1], kv), normal(keys[2], kv)),
        "edge_probe": state.ref.edge_probe(state.seed, t, h, kv, dh, c["sliding_window"]),
    }
    return {name: tuple(rounded(a) for a in qkv) for name, qkv in sets.items()}, normal(keys[3], h)


def _window_gap(state, control=False):
    """(e): the worst rms gap of out, dq, dk, dv over the two sets of inputs,
    between the program's windowed attention (the control: the reference's
    masked form with a window one short and one long, the nearer of the two)
    and the reference's masked form."""
    ref, window = state.ref, state.c["sliding_window"]
    sets, weights = _window_inputs(state)
    names = ("out", "dq", "dk", "dv")
    worst, where = 0.0, ""
    for name, (q, k, v) in sets.items():
        want = _host(ref.attention_and_gradients(q, k, v, window, weights))
        if control:
            if name != "edge_probe":
                continue
            rows = []
            for wrong in (window - 1, window + 1):
                got = _host(ref.attention_and_gradients(q, k, v, wrong, weights))
                rows.append(max(ref.rms_gap(g, w) for g, w in zip(got, want)))
            gaps = {"nearer_wrong_window": min(rows)}
        else:
            got = _host(state.windowed(q, k, v, weights))
            gaps = {n: ref.rms_gap(g, w) for n, g, w in zip(names, got, want)}
        for n, gap in gaps.items():
            if not gap <= worst:  # a NaN is the worst
                worst, where = float(gap), f"{name} {n}"
    print(json.dumps({"reported": "window_gap", "worst": worst, "at": where, "control": control}), flush=True)
    return worst


def expected_biases(state, counts_by_step):
    """The rule on the host, float32, from zero: ``counts_by_step`` holds each
    step's counts (expert layers x experts) in order."""
    rate = np.float32(state.config["bias_rate"])
    bias = np.zeros_like(np.asarray(counts_by_step[0]), dtype=np.float32)
    for counts in counts_by_step:
        counts = np.asarray(counts, np.float32)
        bias = bias + rate * np.sign(counts.mean(axis=-1, keepdims=True, dtype=np.float32) - counts)
    return bias


def _bias_gap(state, got, counts_by_step):
    want = expected_biases(state, counts_by_step)
    gap = float(np.max(np.abs(np.asarray(got, np.float32) - want)) / state.config["bias_rate"])
    print(json.dumps({"reported": "bias_gap", "worst": gap, "steps": len(counts_by_step),
                      "largest_bias": float(np.max(np.abs(want)))}), flush=True)
    return gap


def check(state, calls, last):
    """``lm_step.check`` (a to d) with this kind's trees, then (e) and (f)."""
    by_index = sorted(calls, key=lambda c: c.index)
    biases = _host(biases_of(last.params))  # before the check consumes the state
    with _ThisKind():
        rows = lm_step.check(state, calls, last)
    row = rows[-1][1]
    counts = np.stack([c.summary["expert_counts"] for c in by_index])  # steps x expert layers x experts
    first, held = state.c["first_expert_held"], state.c["num_experts_held"]
    share = counts[:, :, first:first + held].sum(-1) / (counts.sum(-1) * held / state.c["num_experts"])
    print(json.dumps({  # what the step's time follows: the rows that land here, in even shares, and how often past 2
        "reported": "held_share", "mean": float(share.mean()), "largest_by_layer": [float(v) for v in share.max(0)],
        "steps_past_the_first_window": int((share > 2).any(axis=1).sum()), "steps": len(by_index),
    }), flush=True)
    if [c.index for c in by_index] == list(range(len(by_index))):  # every step of the window returned its counts
        row["bias_gap"] = _bias_gap(state, biases, list(counts))
    row["window_gap"] = _window_gap(state)
    return rows


@contextlib.contextmanager
def _reference_with(state, **changed):
    """The reference's model with keys of ``c`` changed, for as long as this runs."""
    true = state.c
    state.c = {**true, **changed}
    try:
        yield
    finally:
        state.c = true


EVALUATED = ("logits_gap", "logits_rms_gap", "loss_gap", "grad_norm_gap", "routing_disagreement")


def control(state, i):
    """One row, as ``lm_step.control`` gives it, of several controls, each
    owning the numbers it is meant to move, all with this kind's trees and all
    at the cell's own size. (1) The reference a precision below the guarantee
    (``products='bf16'``: bfloat16 operands and accumulator in every product,
    bfloat16 norms, sigmoid and weights) against the reference itself: the
    replay's two numbers, and the evaluation's (``EVALUATED``) at the program's
    parameters after ``control_steps`` steps; ``update_gap``: AdamW with
    bfloat16 moments. (2) The controls of the mask and the positions, the
    reference with one thing wrong against the reference itself at the same
    parameters: a window on the full layers (``full_window``), rotary on the
    full layers (``full_rotary``). Each of the three evaluated controls is put
    through the run's own comparison (``chipbench/run.py::compare``, printed
    with the limits it passed) and printed (``control``: its name, its row,
    ``refused_by``); of each evaluated number the row takes the **smallest** of
    the three, so that a limit under the row's value refuses all three by that
    number. (3) ``window_gap``: a window one short or one long on the edge
    probe. (4) ``bias_gap``: a step that leaves the biases where they were."""
    from chipbench.run import compare

    chk = state.config["check"]
    n = chk["replay_steps"]
    with _ThisKind():
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
        for name, changed in (("full_window", {"full_window": state.c["sliding_window"]}), ("full_rotary", {"full_rotary": True})):
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
    row["bias_gap"] = float(np.max(np.abs(expected_biases(state, counts))) / state.config["bias_rate"])
    row["window_gap"] = _window_gap(state, control=True)
    return row
