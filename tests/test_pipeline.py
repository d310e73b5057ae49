"""Tests for MPMD pipeline parallelism (ISSUE 19) — 1F1B/GPipe schedules
over node-group stages with DCN-priced inter-stage hops and elastic resume.

Oracles: schedule tables against hand-derived goldens and structural
invariants; pipelined training against a sequential ``jax.grad`` reference
(loss bit-equal, params float-epsilon); 1F1B against GPipe **bitwise**; the
compiled program's collective-permute pair lists against
``pipeline_hop_cost`` exactly (zero drift, including the DCN split derived
from the emitted source-target pairs); a killed-and-restored run against
the uninterrupted trajectory bitwise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import heat_tpu as ht
from heat_tpu import _knobs as knobs
from heat_tpu import telemetry as tm
from heat_tpu.autotune import cost as at_cost
from heat_tpu.core import program_cache
from heat_tpu.parallel import pipeline as pl
from heat_tpu.parallel import schedule as sch
from heat_tpu.telemetry import collectives as cost_model
from heat_tpu.telemetry import hlo
from heat_tpu.telemetry import report


@pytest.fixture(scope="module")
def comm():
    return ht.get_comm()


def _layer_fn(w, h):
    return jnp.tanh(h @ w["w"] + w["b"])


def _loss_fn(out, y):
    return jnp.mean((out - y) ** 2)


def _make_layers(n_layers, din, seed=0):
    rng = np.random.default_rng(seed)
    return [
        {
            "w": jnp.asarray(rng.standard_normal((din, din)) * 0.3,
                             jnp.float32),
            "b": jnp.asarray(rng.standard_normal((din,)) * 0.1, jnp.float32),
        }
        for _ in range(n_layers)
    ]


def _data(batch, din, seed=1):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((batch, din)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((batch, din)), jnp.float32)
    return x, y


def _ref_loss_grads(layers, mx, my):
    """Sequential reference: same microbatch loop, same loss/M grouping."""
    M = mx.shape[0]

    def f(params_list, xs, ys):
        tot = jnp.zeros((), jnp.float32)
        for m in range(M):
            h = xs[m]
            for w in params_list:
                h = _layer_fn(w, h)
            tot = tot + _loss_fn(h, ys[m]) / M
        return tot

    return jax.value_and_grad(f)(layers, mx, my)


def _tobytes_tree(tree):
    return [np.asarray(l).tobytes() for l in jax.tree_util.tree_leaves(tree)]


def _require_stages(comm, S):
    if comm.size % S:
        pytest.skip(f"{comm.size} devices not divisible into {S} stages")


# -- schedule tables ----------------------------------------------------------


class TestScheduleTable:
    def test_gpipe_golden_s2m2(self):
        t = sch.build_schedule(2, 2, "gpipe")
        assert t.describe() == (
            "s0: F0 F1 .... .... B0 B1\n"
            "s1: .... F0 F1 B0 B1 ...."
        )

    def test_1f1b_golden_s2m2(self):
        t = sch.build_schedule(2, 2, "1f1b")
        assert t.describe() == (
            "s0: F0 F1 .... B0 .... B1\n"
            "s1: .... F0 B0 F1 B1 ...."
        )

    def test_makespan_and_total_bubble_identical(self):
        # Textbook identity: 1F1B does NOT change the makespan or the total
        # bubble — it reorders cells. The honest win is steady-state idle
        # ticks and the stash depth, asserted below.
        for S, M in [(2, 2), (2, 8), (4, 8), (8, 2)]:
            g = sch.build_schedule(S, M, "gpipe")
            f = sch.build_schedule(S, M, "1f1b")
            assert g.n_ticks == f.n_ticks == 2 * (S + M - 1)
            assert g.busy_cells() == f.busy_cells() == 2 * S * M
            assert g.bubble_cells() == f.bubble_cells()
            assert g.bubble_fraction() == f.bubble_fraction()

    def test_steady_bubble_strictly_fewer_at_s4_m8(self):
        # Headline acceptance figure, straight from the tables.
        g = sch.build_schedule(4, 8, "gpipe")
        f = sch.build_schedule(4, 8, "1f1b")
        assert g.steady_bubble_ticks() == 12
        assert f.steady_bubble_ticks() == 10
        assert f.steady_bubble_ticks() < g.steady_bubble_ticks()

    def test_steady_bubble_never_worse(self):
        for S in (2, 4, 8):
            for M in (1, 2, 8):
                g = sch.build_schedule(S, M, "gpipe")
                f = sch.build_schedule(S, M, "1f1b")
                assert f.steady_bubble_ticks() <= g.steady_bubble_ticks()

    def test_stash_depth(self):
        assert sch.build_schedule(4, 8, "gpipe").stash_depth() == 8
        assert sch.build_schedule(4, 8, "1f1b").stash_depth() == 4
        assert sch.build_schedule(4, 2, "1f1b").stash_depth() == 2
        assert sch.build_schedule(4, 8, "gpipe",
                                  train=False).stash_depth() == 1

    def test_validate_grid(self):
        for name in sch.SCHEDULES:
            for S in (1, 2, 4, 8):
                for M in (1, 2, 3, 8):
                    t = sch.build_schedule(S, M, name)
                    assert t.validate() is t

    def test_action_arrays_cover_every_cell_once(self):
        t = sch.build_schedule(4, 8, "1f1b")
        fwd, bwd = t.action_arrays()
        assert len(fwd) == len(bwd) == t.n_ticks
        for s in range(4):
            fcol = [fwd[tt][s] for tt in range(t.n_ticks)]
            bcol = [bwd[tt][s] for tt in range(t.n_ticks)]
            assert sorted(m for m in fcol if m >= 0) == list(range(8))
            assert sorted(m for m in bcol if m >= 0) == list(range(8))

    def test_single_slot_buffer_safety(self):
        # The kernel keeps ONE in-flight message slot per direction: the
        # payload stage s-1 sends for microbatch m must be consumed by
        # stage s before s-1 emits microbatch m+1 (and mirrored for the
        # backward cotangent hop). Both schedules satisfy this.
        for name in sch.SCHEDULES:
            for S, M in [(2, 2), (2, 8), (4, 8), (8, 8), (4, 3)]:
                t = sch.build_schedule(S, M, name)
                fwd, bwd = t.action_arrays()
                ftick = {}
                btick = {}
                for tt in range(t.n_ticks):
                    for s in range(S):
                        if fwd[tt][s] >= 0:
                            ftick[(s, fwd[tt][s])] = tt
                        if bwd[tt][s] >= 0:
                            btick[(s, bwd[tt][s])] = tt
                for s in range(1, S):
                    for m in range(M - 1):
                        assert ftick[(s, m)] <= ftick[(s - 1, m + 1)], (
                            name, S, M, s, m)
                for s in range(S - 1):
                    for m in range(M - 1):
                        assert btick[(s, m)] <= btick[(s + 1, m + 1)], (
                            name, S, M, s, m)

    def test_validate_rejects_broken_tables(self):
        t = sch.build_schedule(2, 2, "gpipe")
        # flip every F<->B at stage 1: backwards now precede forwards
        flipped = tuple(
            tuple(
                sch.Action("B" if a.kind == "F" else "F", a.mb)
                if a is not None and s == 1 else a
                for s, a in enumerate(row)
            )
            for row in t.ticks
        )
        with pytest.raises(ValueError):
            sch.ScheduleTable("gpipe", 2, 2, True, flipped).validate()
        # duplicate cell
        dup = t.ticks[:1] + t.ticks
        with pytest.raises(ValueError, match="duplicate"):
            sch.ScheduleTable("gpipe", 2, 2, True, dup).validate()

    def test_phase_partition(self):
        t = sch.build_schedule(4, 8, "1f1b")
        lo, hi = t.steady_window()
        assert 0 <= lo <= hi < t.n_ticks
        phases = [t.phase_of(tt) for tt in range(t.n_ticks)]
        assert phases[0] == "warmup" and phases[-1] == "cooldown"
        assert all(p == "steady" for p in phases[lo:hi + 1])

    def test_forward_only_is_gpipe_wave(self):
        t = sch.build_schedule(4, 8, "1f1b", train=False)
        assert not t.train
        assert t.n_ticks == 4 + 8 - 1
        assert t.busy_cells() == 4 * 8
        assert t.bubble_cells() == t.n_ticks * 4 - 4 * 8

    def test_resolve_schedule_name(self, monkeypatch):
        assert sch.resolve_schedule_name() == "gpipe"
        assert sch.resolve_schedule_name("1f1b") == "1f1b"
        monkeypatch.setenv("HEAT_TPU_PIPELINE_SCHEDULE", "1f1b")
        assert sch.resolve_schedule_name() == "1f1b"
        with pytest.raises(ValueError):
            sch.resolve_schedule_name("interleaved")

    def test_bad_shapes_rejected(self):
        with pytest.raises(ValueError):
            sch.build_schedule(0, 4, "gpipe")
        with pytest.raises(ValueError):
            sch.build_schedule(4, 0, "gpipe")


class TestStageMapping:
    def test_groups_and_perms(self):
        m = sch.StageMapping(8, 4)
        assert m.local == 2
        assert m.groups() == [[0, 1], [2, 3], [4, 5], [6, 7]]
        assert m.fwd_perm() == [(i, (i + 2) % 8) for i in range(8)]
        assert sorted(m.bwd_perm()) == sorted(
            [((i + 2) % 8, i) for i in range(8)])
        assert m.describe() == "4x2"

    def test_indivisible_rejected(self):
        with pytest.raises(ValueError):
            sch.StageMapping(8, 3)

    def test_plan_stages_default_one_per_proc(self):
        assert sch.plan_stages(8).n_stages == 8
        assert sch.plan_stages(8).local == 1

    def test_plan_stages_knob(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_PIPELINE_STAGES", "2")
        m = sch.plan_stages(8)
        assert (m.n_stages, m.local) == (2, 4)

    def test_plan_stages_auto_follows_node_groups(self, monkeypatch):
        monkeypatch.setenv("HEAT_TPU_PIPELINE_STAGES", "0")
        monkeypatch.setenv("HEAT_TPU_HIERARCHICAL", "1")
        monkeypatch.setenv("HEAT_TPU_TOPOLOGY", "4x2")
        m = sch.plan_stages(8)
        assert (m.n_stages, m.local) == (4, 2)


# -- layout / shard roundtrip -------------------------------------------------


class TestLayout:
    def test_roundtrip_bitwise(self, comm):
        S = comm.size
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(2 * S, 6)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        back = pl.unshard_pipeline_params(rows, layout)
        assert len(back) == 2 * S
        for a, b in zip(layers, back):
            for k in ("w", "b"):
                assert np.asarray(a[k]).tobytes() == np.asarray(b[k]).tobytes()

    def test_bytes_per_device_counts_chunks(self, comm):
        mapping = sch.StageMapping(comm.size, comm.size)
        layers = _make_layers(comm.size, 4)
        layout = pl.plan_pipeline(layers, mapping)
        per_leaf = sum(
            layout.layers_per_stage * layout.chunk(k) * 4
            for k in range(len(layout.shapes))
        )
        assert layout.bytes_per_device() == per_leaf

    def test_heterogeneous_layers_rejected(self, comm):
        if comm.size < 2:
            pytest.skip("needs >= 2 layers to differ")
        mapping = sch.StageMapping(comm.size, comm.size)
        layers = _make_layers(comm.size, 4)
        layers[-1] = {"w": layers[-1]["w"], "b": jnp.zeros((5,), jnp.float32)}
        with pytest.raises(ValueError, match="homogeneous"):
            pl.plan_pipeline(layers, mapping)

    def test_layer_count_must_divide(self, comm):
        if comm.size < 2:
            pytest.skip("needs >= 2 stages")
        mapping = sch.StageMapping(comm.size, comm.size)
        with pytest.raises(ValueError):
            pl.plan_pipeline(_make_layers(comm.size + 1, 4), mapping)

    def test_wire_coercion(self, comm):
        mapping = sch.StageMapping(comm.size, comm.size)
        layers = _make_layers(comm.size, 4)
        assert pl.plan_pipeline(layers, mapping, wire="int8").wire == "bf16"
        assert pl.plan_pipeline(layers, mapping, wire="off").wire == "off"
        with pytest.raises(ValueError):
            pl.plan_pipeline(layers, mapping, wire="fp4")


# -- training-step parity -----------------------------------------------------


def _run_step(comm, S, M, schedule, *, layers=None, din=6, lps=1, seed=0):
    mapping = sch.StageMapping(comm.size, S)
    if layers is None:
        layers = _make_layers(lps * S, din, seed=seed)
    opt = optax.adam(1e-2)
    layout = pl.plan_pipeline(layers, mapping)
    rows = pl.shard_pipeline_params(layers, layout, comm)
    st = opt.init(rows)
    x, y = _data(2 * M, din, seed=seed + 1)
    mx = x.reshape(M, 2, din)
    my = y.reshape(M, 2, din)
    table = sch.build_schedule(S, M, schedule)
    step = pl.pipeline_step_program(
        _layer_fn, layout, mapping, table, comm=comm,
        loss_fn=_loss_fn, optimizer=opt,
    )
    p2, s2, loss = step(rows, st, mx, my)
    return layers, layout, (p2, s2, loss), (mx, my), opt


class TestStepParity:
    @pytest.mark.parametrize("S", [2, 4, 8])
    @pytest.mark.parametrize("M", [1, 2, 8])
    def test_gpipe_matches_sequential(self, comm, S, M):
        _require_stages(comm, S)
        layers, layout, (p2, _, loss), (mx, my), opt = _run_step(
            comm, S, M, "gpipe")
        ref_loss, ref_g = _ref_loss_grads(layers, mx, my)
        # the microbatch loss accumulator follows the identical op order
        assert np.asarray(loss).tobytes() == np.asarray(ref_loss).tobytes()
        ups, _ = opt.update(ref_g, opt.init(layers), layers)
        refp = optax.apply_updates(layers, ups)
        got = pl.unshard_pipeline_params(p2, layout)
        for j, (a, b) in enumerate(zip(got, refp)):
            for k in ("w", "b"):
                np.testing.assert_allclose(
                    np.asarray(a[k]), np.asarray(b[k]),
                    rtol=1e-6, atol=1e-7, err_msg=f"layer {j} leaf {k}")

    @pytest.mark.parametrize("S,M", [(2, 2), (4, 8), (8, 2)])
    def test_1f1b_bit_identical_to_gpipe(self, comm, S, M):
        _require_stages(comm, S)
        _, _, (pg, sg, lg), _, _ = _run_step(comm, S, M, "gpipe")
        _, _, (pf, sf, lf), _, _ = _run_step(comm, S, M, "1f1b")
        assert np.asarray(lg).tobytes() == np.asarray(lf).tobytes()
        assert _tobytes_tree(pg) == _tobytes_tree(pf)
        assert _tobytes_tree(sg) == _tobytes_tree(sf)

    def test_padded_activation_rank3(self, comm):
        # padded / odd activation shapes: (B, 3, 5) with din=5 features
        S = comm.size if comm.size in (2, 4, 8) else None
        if S is None:
            pytest.skip("needs a mesh of 2/4/8 for this shape battery")
        M = 2
        mapping = sch.StageMapping(comm.size, S)
        rng = np.random.default_rng(7)
        layers = [
            {"w": jnp.asarray(rng.standard_normal((5, 5)) * 0.3, jnp.float32),
             "b": jnp.asarray(rng.standard_normal((5,)) * 0.1, jnp.float32)}
            for _ in range(S)
        ]
        opt = optax.adam(1e-2)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        st = opt.init(rows)
        x = jnp.asarray(rng.standard_normal((2 * M, 3, 5)), jnp.float32)
        y = jnp.asarray(rng.standard_normal((2 * M, 3, 5)), jnp.float32)
        mx, my = x.reshape(M, 2, 3, 5), y.reshape(M, 2, 3, 5)
        table = sch.build_schedule(S, M, "1f1b")
        step = pl.pipeline_step_program(
            _layer_fn, layout, mapping, table, comm=comm,
            loss_fn=_loss_fn, optimizer=opt)
        _, _, loss = step(rows, st, mx, my)
        ref_loss, _ = _ref_loss_grads(layers, mx, my)
        assert np.asarray(loss).tobytes() == np.asarray(ref_loss).tobytes()

    def test_forward_only_matches_sequential(self, comm):
        S = comm.size
        M = 2
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(S, 6, seed=3)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        x, _ = _data(2 * M, 6, seed=4)
        mx = x.reshape(M, 2, 6)
        table = sch.build_schedule(S, M, "gpipe", train=False)
        fwd = pl.pipeline_step_program(
            _layer_fn, layout, mapping, table, comm=comm)
        out = fwd(rows, mx)
        h = x
        for w in layers:
            h = _layer_fn(w, h)
        np.testing.assert_allclose(
            np.asarray(out).reshape(2 * M, 6), np.asarray(h),
            rtol=1e-6, atol=1e-7)


# -- recompile oracles --------------------------------------------------------


class TestZeroRecompile:
    def test_pipeline_apply_site_cached(self, comm):
        d = 4
        layers = _make_layers(comm.size, d, seed=9)
        stacked = pl.stack_stage_params(layers)
        x, _ = _data(8, d, seed=10)

        def stage_fn(w, h):
            return jnp.tanh(h @ w["w"] + w["b"])

        y0 = pl.pipeline_apply(stage_fn, stacked, x, comm=comm,
                               n_microbatches=4)
        before = program_cache.site_stats("pipeline.apply")
        with tm.CompileWatcher() as w:
            x2, _ = _data(8, d, seed=11)
            y1 = pl.pipeline_apply(stage_fn, stacked, x2, comm=comm,
                                   n_microbatches=4)
        after = program_cache.site_stats("pipeline.apply")
        assert after["misses"] == before["misses"]
        assert after["hits"] == before["hits"] + 1
        assert w.backend_seconds == 0.0
        assert y0.shape == y1.shape

    def test_pipeline_step_zero_steady_compiles(self, comm):
        S = comm.size
        M = 2
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(S, 4, seed=12)
        opt = optax.adam(1e-2)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        st = opt.init(rows)
        x, y = _data(2 * M, 4, seed=13)
        mx, my = x.reshape(M, 2, 4), y.reshape(M, 2, 4)
        table = sch.build_schedule(S, M, "gpipe")
        step = pl.pipeline_step_program(
            _layer_fn, layout, mapping, table, comm=comm,
            loss_fn=_loss_fn, optimizer=opt)
        # two warm steps: the first compiles the program, the second the
        # steady input layouts (step outputs carry device shardings the
        # freshly-sharded inputs did not)
        for _ in range(2):
            rows, st, _ = step(rows, st, mx, my)
        before = program_cache.site_stats("pipeline.step")
        # a second program build with the same static config must be a
        # registry hit, and steady-state steps must never touch the backend
        step2 = pl.pipeline_step_program(
            _layer_fn, layout, mapping, table, comm=comm,
            loss_fn=_loss_fn, optimizer=opt)
        with tm.CompileWatcher() as w:
            for _ in range(3):
                rows, st, _ = step2(rows, st, mx, my)
        after = program_cache.site_stats("pipeline.step")
        assert after["misses"] == before["misses"]
        assert after["hits"] == before["hits"] + 1
        assert w.backend_seconds == 0.0
        assert w.stages.get("backend_compile_duration", 0.0) == 0.0


# -- HLO audit: inter-stage hop zero-drift ------------------------------------


def _audit_step(comm, S, M):
    mapping = sch.StageMapping(comm.size, S)
    layers = _make_layers(mapping.n_stages, 6, seed=20)
    opt = optax.adam(1e-2)
    layout = pl.plan_pipeline(layers, mapping)
    rows = pl.shard_pipeline_params(layers, layout, comm)
    st = opt.init(rows)
    x, y = _data(2 * M, 6, seed=21)
    mx, my = x.reshape(M, 2, 6), y.reshape(M, 2, 6)
    table = sch.build_schedule(S, M, "gpipe")
    step = pl.pipeline_step_program(
        _layer_fn, layout, mapping, table, comm=comm,
        loss_fn=_loss_fn, optimizer=opt)
    audit = hlo.audit_computation(step, rows, st, mx, my)
    return mapping, table, audit


class TestHopAuditZeroDrift:
    def test_permute_bytes_match_hop_cost_exactly(self, comm):
        if comm.size < 2:
            pytest.skip("no inter-stage hop on one device")
        S, M = comm.size, 2
        mapping, table, audit = _audit_step(comm, S, M)
        perms = [c for c in audit.collectives
                 if c.op == "collective-permute"]
        # one fwd + one bwd permute per tick, fully unrolled; the final
        # tick ships nothing (no consumer), hence n_ticks - 1
        assert len(perms) == 2 * (table.n_ticks - 1)
        hop = cost_model.pipeline_hop_cost(
            2, 6, 4, comm.size, stride=mapping.local)
        assert hop.kind == "ppermute-ring"
        for c in perms:
            assert len(c.groups) == comm.size
            assert c.wire_bytes == hop.bytes
        total = sum(c.wire_bytes for c in perms)
        assert total == 2 * (table.n_ticks - 1) * hop.bytes

    def test_dcn_split_matches_emitted_pairs(self, comm, monkeypatch):
        if comm.size != 8:
            pytest.skip("topology split pinned to an 8-proc mesh")
        monkeypatch.setenv("HEAT_TPU_HIERARCHICAL", "1")
        monkeypatch.setenv("HEAT_TPU_TOPOLOGY", "4x2")
        S, M = 4, 2
        mapping, table, audit = _audit_step(comm, S, M)
        node_local = 2
        hop = cost_model.pipeline_hop_cost(
            2, 6, 4, comm.size, stride=mapping.local, local=node_local)
        # stage == node group and stride == local: every pair crosses
        assert hop.dcn_bytes == hop.bytes
        perms = [c for c in audit.collectives
                 if c.op == "collective-permute"]
        assert perms
        emitted_dcn = 0
        emitted_total = 0
        for c in perms:
            pairs = [tuple(pr) for pr in c.groups]
            per_pair = c.wire_bytes // len(pairs)
            assert per_pair * len(pairs) == c.wire_bytes
            cross = [pr for pr in pairs
                     if pr[0] // node_local != pr[1] // node_local]
            emitted_dcn += per_pair * len(cross)
            emitted_total += c.wire_bytes
        assert emitted_total == 2 * (table.n_ticks - 1) * hop.bytes
        assert emitted_dcn == 2 * (table.n_ticks - 1) * hop.dcn_bytes

    def test_flat_mesh_prices_zero_dcn(self, comm):
        hop = cost_model.pipeline_hop_cost(2, 6, 4, comm.size, stride=1)
        assert hop.dcn_bytes == 0


# -- activation-memory watermark ----------------------------------------------


class TestActivationWatermark:
    def test_1f1b_watermark_strictly_below_gpipe(self, comm):
        _require_stages(comm, 4)
        S, M, din = 4, 8, 8
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(S, din, seed=30)
        opt = optax.adam(1e-2)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        st = opt.init(rows)
        # 256-row microbatches (8 KB each): the stash must dominate the
        # compiler's own scratch, or the comparison reads allocator noise
        mb = 256
        x, y = _data(mb * M, din, seed=31)
        mx, my = x.reshape(M, mb, din), y.reshape(M, mb, din)

        def temp_bytes(name):
            table = sch.build_schedule(S, M, name)
            step = pl.pipeline_step_program(
                _layer_fn, layout, mapping, table, comm=comm,
                loss_fn=_loss_fn, optimizer=opt)
            # heatlint: disable=HL001 -- one-shot lowering for the
            # memory_analysis watermark, never executed
            compiled = jax.jit(step).lower(rows, st, mx, my).compile()
            ma = compiled.memory_analysis()
            return int(getattr(ma, "temp_size_in_bytes", 0) or 0)

        g = temp_bytes("gpipe")
        f = temp_bytes("1f1b")
        if g == 0 or f == 0:
            pytest.skip("backend reports no memory analysis")
        # gpipe stashes all M in-flight microbatch inputs; 1f1b caps the
        # stash at min(S, M) — the watermark must be strictly lower.
        assert f < g, (f, g)


# -- telemetry: per-tick spans + gather pricing -------------------------------


class TestTelemetry:
    def test_tick_events_match_table(self, comm, tmp_path):
        S = comm.size
        M = 4
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(S, 4, seed=40)
        opt = optax.adam(1e-2)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        st = opt.init(rows)
        x, y = _data(2 * M, 4, seed=41)
        mx, my = x.reshape(M, 2, 4), y.reshape(M, 2, 4)
        table = sch.build_schedule(S, M, "1f1b")

        # a fresh (locally-defined) layer fn forces a fresh trace so the
        # trace-time tick events are emitted under telemetry
        def local_layer(w, h):
            return jnp.tanh(h @ w["w"] + w["b"])

        path = str(tmp_path / "pipe_events.jsonl")
        reg = tm.enable(path)
        n0 = len(reg.events)
        try:
            step = pl.pipeline_step_program(
                local_layer, layout, mapping, table, comm=comm,
                loss_fn=_loss_fn, optimizer=opt)
            step(rows, st, mx, my)
            events = list(reg.events)[n0:]
        finally:
            tm.disable()
        ticks = [e for e in events if e.get("name") == "pipeline_tick"]
        assert len(ticks) == table.n_ticks
        if S > 1:  # a 1-stage pipeline never idles
            assert sum(1 for e in ticks if e["bubble"] > 0) > 0
        steady_bubbles = sum(
            e["bubble"] for e in ticks if e["phase"] == "steady")
        assert steady_bubbles == table.steady_bubble_ticks()
        hop = cost_model.pipeline_hop_cost(2, 4, 4, comm.size,
                                           stride=mapping.local)
        for e in ticks:
            assert e["schedule"] == "1f1b"
            assert e["hops"] == (2 if e["tick"] < table.n_ticks - 1 else 0)
            assert e["hop_bytes"] == hop.bytes
        summary = report.summarize(events)
        block = summary["pipeline"]["schedules"]["1f1b"]
        assert block["ticks"] == table.n_ticks
        assert block["steady_bubble_cells"] == table.steady_bubble_ticks()
        assert block["hop_bytes"] == 2 * (table.n_ticks - 1) * hop.bytes

    def test_measured_steady_bubbles_rank_schedules(self, comm, tmp_path):
        # acceptance: the 1F1B win must ALSO show up in per-tick telemetry
        _require_stages(comm, 4)
        S, M = 4, 8
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(S, 4, seed=42)
        opt = optax.adam(1e-2)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        st = opt.init(rows)
        x, y = _data(2 * M, 4, seed=43)
        mx, my = x.reshape(M, 2, 4), y.reshape(M, 2, 4)

        def measure(name):
            def local_layer(w, h):
                return jnp.tanh(h @ w["w"] + w["b"])

            path = str(tmp_path / f"ev_{name}.jsonl")
            reg = tm.enable(path)
            n0 = len(reg.events)
            try:
                table = sch.build_schedule(S, M, name)
                step = pl.pipeline_step_program(
                    local_layer, layout, mapping, table, comm=comm,
                    loss_fn=_loss_fn, optimizer=opt)
                step(rows, st, mx, my)
                events = list(reg.events)[n0:]
            finally:
                tm.disable()
            return sum(e["bubble"] for e in events
                       if e.get("name") == "pipeline_tick"
                       and e["phase"] == "steady")

        assert measure("1f1b") == 10
        assert measure("gpipe") == 12

    def test_gather_events_priced(self, comm, tmp_path):
        if comm.size < 2 or comm.size % 2:
            pytest.skip("needs an even mesh for a 2-wide stage group")
        S = comm.size // 2
        mapping = sch.StageMapping(comm.size, S)
        layers = _make_layers(S, 4, seed=44)
        layout = pl.plan_pipeline(layers, mapping)
        rows = pl.shard_pipeline_params(layers, layout, comm)
        x, _ = _data(4, 4, seed=45)
        mx = x.reshape(2, 2, 4)

        def local_layer(w, h):
            return jnp.tanh(h @ w["w"] + w["b"])

        path = str(tmp_path / "gather.jsonl")
        reg = tm.enable(path)
        n0 = len(reg.events)
        try:
            table = sch.build_schedule(S, 2, "gpipe", train=False)
            fwd = pl.pipeline_step_program(
                local_layer, layout, mapping, table, comm=comm)
            fwd(rows, mx)
            events = list(reg.events)[n0:]
        finally:
            tm.disable()
        gathers = [e for e in events if e.get("name") == "pipeline_gather"]
        assert gathers
        for e in gathers:
            assert e["collective"] == "all-gather"
            assert e["bytes"] > 0
            assert e["group"] == mapping.describe()
        summary = report.summarize(events)
        assert summary["pipeline"]["gather_events"] == len(gathers)
        assert summary["pipeline"]["gather_bytes"] == sum(
            e["bytes"] for e in gathers)


# -- elastic checkpoint / resume ----------------------------------------------


class TestElasticResume:
    def test_restore_across_factorization_bitwise(self, comm, tmp_path):
        # headline acceptance: kill after step 2, restore the logical
        # checkpoint onto a DIFFERENT node x local factorization AND a
        # different schedule, and the continued trajectory must be
        # bit-identical to the uninterrupted one.
        if comm.size % 4:
            pytest.skip("needs a mesh divisible by 4 for two factorizations")
        from heat_tpu.nn import Pipeline

        L, din = 4, 8
        layers = _make_layers(L, din, seed=50)
        opt = optax.adam(1e-2)
        x, y = _data(16, din, seed=51)

        pipe_a = Pipeline(_layer_fn, L, comm, opt, _loss_fn,
                          n_stages=4, n_microbatches=8, schedule="1f1b")
        rows = pipe_a.shard_params(layers)
        st = pipe_a.init_opt_state(rows)
        step = pipe_a.make_train_step()
        for _ in range(2):
            rows, st, _ = step(rows, st, x, y)
        ckpt = str(tmp_path / "elastic_ckpt")
        pipe_a.save_checkpoint(ckpt, rows, st, step=2)
        for _ in range(2):
            rows, st, loss_a = step(rows, st, x, y)
        final_a = pipe_a.unshard_params(rows)

        pipe_b = Pipeline(_layer_fn, L, comm, opt, _loss_fn,
                          n_stages=2, n_microbatches=8, schedule="gpipe")
        rows_b, st_b, cursor = pipe_b.resume(ckpt, layers)
        assert cursor == 2
        step_b = pipe_b.make_train_step()
        for _ in range(2):
            rows_b, st_b, loss_b = step_b(rows_b, st_b, x, y)
        final_b = pipe_b.unshard_params(rows_b)

        assert np.asarray(loss_a).tobytes() == np.asarray(loss_b).tobytes()
        for ja, jb in zip(final_a, final_b):
            for k in ("w", "b"):
                assert (np.asarray(ja[k]).tobytes()
                        == np.asarray(jb[k]).tobytes())

    def test_resume_rejects_mismatched_model(self, comm, tmp_path):
        from heat_tpu.nn import Pipeline

        L, din = comm.size, 4
        layers = _make_layers(L, din, seed=52)
        opt = optax.adam(1e-2)
        pipe = Pipeline(_layer_fn, L, comm, opt, _loss_fn, n_stages=comm.size,
                        n_microbatches=2)
        rows = pipe.shard_params(layers)
        st = pipe.init_opt_state(rows)
        ckpt = str(tmp_path / "mismatch_ckpt")
        pipe.save_checkpoint(ckpt, rows, st, step=1)
        from heat_tpu import resilience

        other = Pipeline(_layer_fn, 2 * L, comm, opt, _loss_fn,
                         n_stages=comm.size, n_microbatches=2)
        with pytest.raises(resilience.CheckpointError, match="layers"):
            other.resume(ckpt, _make_layers(2 * L, din))


# -- ht.nn.Pipeline front end -------------------------------------------------


class TestPipelineFrontEnd:
    def test_forward_call_matches_sequential(self, comm):
        from heat_tpu.nn import Pipeline

        L, din = comm.size, 6
        layers = _make_layers(L, din, seed=60)
        pipe = Pipeline(_layer_fn, L, comm, n_stages=comm.size,
                        n_microbatches=2)
        rows = pipe.shard_params(layers)
        x, _ = _data(4, din, seed=61)
        out = pipe(rows, x)
        h = x
        for w in layers:
            h = _layer_fn(w, h)
        np.testing.assert_allclose(np.asarray(out), np.asarray(h),
                                   rtol=1e-6, atol=1e-7)

    def test_microbatches_default_to_stage_count(self, comm):
        from heat_tpu.nn import Pipeline

        pipe = Pipeline(_layer_fn, comm.size, comm, n_stages=comm.size)
        assert pipe.n_microbatches == comm.size

    def test_schedule_knob_resolution(self, comm, monkeypatch):
        from heat_tpu.nn import Pipeline

        monkeypatch.setenv("HEAT_TPU_PIPELINE_SCHEDULE", "1f1b")
        pipe = Pipeline(_layer_fn, comm.size, comm, n_stages=comm.size)
        assert pipe.schedule == "1f1b"

    def test_layers_must_divide_stages(self, comm):
        from heat_tpu.nn import Pipeline

        if comm.size < 2:
            pytest.skip("needs >= 2 stages")
        with pytest.raises(ValueError, match="divide"):
            Pipeline(_layer_fn, comm.size + 1, comm, n_stages=comm.size)

    def test_layout_requires_plan(self, comm):
        from heat_tpu.nn import Pipeline

        pipe = Pipeline(_layer_fn, comm.size, comm, n_stages=comm.size)
        with pytest.raises(ValueError, match="layout"):
            _ = pipe.layout

    def test_bare_callable_init_rejected(self, comm):
        from heat_tpu.nn import Pipeline

        pipe = Pipeline(_layer_fn, comm.size, comm, n_stages=comm.size)
        with pytest.raises(TypeError, match="bare callable"):
            pipe.init(jax.random.PRNGKey(0), jnp.zeros((2, 4)))

    def test_flax_layer_init_and_step(self, comm):
        import flax.linen as nn
        from heat_tpu.nn import Pipeline

        L, din = comm.size, 4
        pipe = Pipeline(nn.Dense(din), L, comm, optax.adam(1e-2), _loss_fn,
                        n_stages=comm.size, n_microbatches=2)
        params = pipe.init(jax.random.PRNGKey(0), jnp.zeros((2, din)))
        assert len(params) == L
        rows = pipe.shard_params(params)
        st = pipe.init_opt_state(rows)
        x, y = _data(4, din, seed=62)
        rows, st, loss = pipe.make_train_step()(rows, st, x, y)
        assert np.isfinite(float(loss))


# -- autotune cost lattice ----------------------------------------------------


class TestPipelineCostFn:
    def _fn(self, **kw):
        kw.setdefault("n_stages", 4)
        return at_cost.pipeline_cost_fn([64, 8], 4, 16, 8, 4, 8, **kw)

    def test_ranks_1f1b_below_gpipe(self):
        fn = self._fn()
        g = fn({"HEAT_TPU_PIPELINE_SCHEDULE": "gpipe",
                "HEAT_TPU_PIPELINE_MICROBATCHES": "8"})
        f = fn({"HEAT_TPU_PIPELINE_SCHEDULE": "1f1b",
                "HEAT_TPU_PIPELINE_MICROBATCHES": "8"})
        assert f < g < float("inf")

    def test_indivisible_microbatches_pruned(self):
        fn = self._fn()
        assert fn({"HEAT_TPU_PIPELINE_SCHEDULE": "gpipe",
                   "HEAT_TPU_PIPELINE_MICROBATCHES": "7"}) == float("inf")

    def test_unknown_schedule_pruned(self):
        fn = self._fn()
        assert fn({"HEAT_TPU_PIPELINE_SCHEDULE": "zigzag"}) == float("inf")

    def test_stash_budget_prunes_gpipe_first(self):
        # at S=4, M=8, mb=2, feat=8, f32: gpipe stash 8*64B, 1f1b 4*64B —
        # a budget between the two keeps only 1f1b feasible
        fn = self._fn(budget=5 * 2 * 8 * 4)
        cfg = {"HEAT_TPU_PIPELINE_MICROBATCHES": "8"}
        g = fn(dict(cfg, HEAT_TPU_PIPELINE_SCHEDULE="gpipe"))
        f = fn(dict(cfg, HEAT_TPU_PIPELINE_SCHEDULE="1f1b"))
        assert g == float("inf")
        assert f < float("inf")

    def test_prefetch_hides_forward_gathers(self):
        fn = self._fn()
        cfg = {"HEAT_TPU_PIPELINE_SCHEDULE": "1f1b",
               "HEAT_TPU_PIPELINE_MICROBATCHES": "8"}
        d0 = fn(dict(cfg, HEAT_TPU_FSDP_PREFETCH="0"))
        d2 = fn(dict(cfg, HEAT_TPU_FSDP_PREFETCH="2"))
        assert d2 < d0

    def test_stage_count_from_config_knob(self):
        fn = at_cost.pipeline_cost_fn([64, 8], 4, 16, 8, 4, 8)
        ok = fn({"HEAT_TPU_PIPELINE_STAGES": "4",
                 "HEAT_TPU_PIPELINE_SCHEDULE": "gpipe"})
        bad = fn({"HEAT_TPU_PIPELINE_STAGES": "3",
                  "HEAT_TPU_PIPELINE_SCHEDULE": "gpipe"})
        assert ok < float("inf")
        assert bad == float("inf")

    def test_dcn_premium_prices_hier_hops(self):
        fn = self._fn()
        base = {"HEAT_TPU_PIPELINE_SCHEDULE": "gpipe",
                "HEAT_TPU_PIPELINE_MICROBATCHES": "8",
                "HEAT_TPU_TOPOLOGY": "4x2"}
        flat = fn(dict(base, HEAT_TPU_HIERARCHICAL="0"))
        tiered = fn(dict(base, HEAT_TPU_HIERARCHICAL="1",
                         HEAT_TPU_DCN_PREMIUM="8"))
        assert tiered > flat


# -- knob registry ------------------------------------------------------------


class TestKnobs:
    def test_pipeline_knobs_registered(self):
        reg = knobs.REGISTRY
        assert reg["HEAT_TPU_PIPELINE_SCHEDULE"].default == "gpipe"
        assert reg["HEAT_TPU_PIPELINE_SCHEDULE"].choices == ("gpipe", "1f1b")
        assert reg["HEAT_TPU_PIPELINE_SCHEDULE"].tunable is not None
        assert reg["HEAT_TPU_PIPELINE_SCHEDULE"].tunable.kind == "exact"
        assert reg["HEAT_TPU_PIPELINE_MICROBATCHES"].tunable is not None
        assert reg["HEAT_TPU_PIPELINE_MICROBATCHES"].tunable.kind == "neutral"
        assert reg["HEAT_TPU_PIPELINE_STAGES"].default == 0
        assert "HEAT_TPU_CI_SKIP_PIPELINE" in reg
