"""Compressed update transport (DESIGN.md §12): quantization error bounds,
error-feedback telescoping, top-k sparsification residuals, cross-engine
equivalence under a FIXED transport config, mid-buffer save/restore with
non-empty accumulators, and checkpoint back-compat for pre-transport
checkpoints.

Equivalence philosophy: compression is a step function (int8 rounding),
and the SVD realloc downstream has sign/rotation freedom, so comparing a
COMPRESSED run against an UNCOMPRESSED run on raw factors is ill-posed --
1-ulp input differences flip rounding decisions and singular-vector signs.
The invariants that ARE exact: (a) the same transport config produces
identical traces on the sequential and batched engines (same host-side
encode order); (b) identical quantized inputs aggregate identically across
backends and meshes; (c) a restored run continues bit-compatibly.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypothesis_compat import given, settings, strategies as st

from repro.federation.experiment import build_experiment
from repro.federation.transport import (QuantFactor, TransportConfig,
                                        UpdateTransport, _encode_pair,
                                        dequantize, is_quantized)

# ---------------------------------------------------------------------------
# quantization layer
# ---------------------------------------------------------------------------


def _rand_pair(rng, d=16, r=8, n=12, zero_cols=0):
    b = rng.normal(size=(d, r)).astype(np.float32)
    a = rng.normal(size=(r, n)).astype(np.float32)
    if zero_cols:
        b[:, r - zero_cols:] = 0.0
        a[r - zero_cols:, :] = 0.0
    return b, a


class TestQuantizeRoundtrip:
    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000),
           zero_cols=st.integers(min_value=0, max_value=4))
    def test_int8_error_bounded_by_half_scale(self, seed, zero_cols):
        """|x - deq(Q(x))| <= scale/2 elementwise: the absmax grid covers
        the column's range, so rounding is the only error source."""
        rng = np.random.default_rng(seed)
        b, a = _rand_pair(rng, zero_cols=zero_cols)
        zb, za = np.zeros_like(b), np.zeros_like(a)
        qb, qa, _, _ = _encode_pair(jnp.asarray(b), jnp.asarray(a), zb, za,
                                    mode="int8", top_k=None)
        for x, qf in ((b, qb), (a, qa)):
            err = np.abs(x - np.asarray(dequantize(qf)))
            bound = np.broadcast_to(np.asarray(qf.scale) / 2.0, x.shape)
            assert (err <= bound + 1e-7).all()

    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_zero_rank_columns_decode_exactly_zero(self, seed):
        """Rank-level awareness for free: columns beyond a client's r_k are
        all-zero under masked training, get scale 0, decode to exact 0 --
        so omega's zero-columns stay zero bit-for-bit."""
        rng = np.random.default_rng(seed)
        b, a = _rand_pair(rng, zero_cols=3)
        zb, za = np.zeros_like(b), np.zeros_like(a)
        qb, qa, _, _ = _encode_pair(jnp.asarray(b), jnp.asarray(a), zb, za,
                                    mode="int8", top_k=None)
        assert (np.asarray(qb.scale)[..., -3:] == 0.0).all()
        assert (np.asarray(dequantize(qb))[:, -3:] == 0.0).all()
        assert (np.asarray(dequantize(qa))[-3:, :] == 0.0).all()

    def test_bf16_mode_unit_scales(self):
        rng = np.random.default_rng(0)
        b, a = _rand_pair(rng)
        qb, qa, _, _ = _encode_pair(jnp.asarray(b), jnp.asarray(a),
                                    np.zeros_like(b), np.zeros_like(a),
                                    mode="bf16", top_k=None)
        assert qb.q.dtype == jnp.bfloat16 and (np.asarray(qb.scale) == 1).all()
        np.testing.assert_allclose(np.asarray(dequantize(qa)), a,
                                   rtol=1e-2, atol=1e-2)


class TestErrorFeedback:
    @settings(max_examples=6, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000),
           rounds=st.integers(min_value=2, max_value=6))
    def test_residuals_telescope(self, seed, rounds):
        """sum_t deq(q_t) == sum_t x_t + e_0 - e_K: the compressed SUM
        tracks the uncompressed sum to within one residual, so compression
        noise does not accumulate across rounds."""
        rng = np.random.default_rng(seed)
        eb = np.zeros((16, 8), np.float32)
        ea = np.zeros((8, 12), np.float32)
        sum_x_b = np.zeros_like(eb)
        sum_q_b = np.zeros_like(eb)
        for _ in range(rounds):
            b, a = _rand_pair(rng)
            qb, qa, rb, ra = _encode_pair(jnp.asarray(b), jnp.asarray(a),
                                          eb, ea, mode="int8", top_k=None)
            sum_x_b += b
            sum_q_b += np.asarray(dequantize(qb))
            eb, ea = np.asarray(rb), np.asarray(ra)
        np.testing.assert_allclose(sum_q_b + eb, sum_x_b,
                                   rtol=1e-5, atol=1e-5)

    def test_topk_drops_into_residual(self):
        """Top-k keeps the k most energetic rank columns; the dropped
        columns' full mass lands in the residual and re-enters next round."""
        rng = np.random.default_rng(3)
        b, a = _rand_pair(rng)
        b[:, 0] *= 10.0; b[:, 1] *= 10.0          # two dominant columns
        qb, qa, rb, ra = _encode_pair(jnp.asarray(b), jnp.asarray(a),
                                      np.zeros_like(b), np.zeros_like(a),
                                      mode="int8", top_k=2)
        kept = np.asarray(qb.scale)[0] > 0
        assert kept.sum() == 2 and kept[0] and kept[1]
        # dropped columns: deq == 0, residual == x exactly
        np.testing.assert_array_equal(np.asarray(rb)[:, ~kept], b[:, ~kept])
        np.testing.assert_array_equal(np.asarray(ra)[~kept, :], a[~kept, :])


# ---------------------------------------------------------------------------
# engine matrix under a FIXED transport config
# ---------------------------------------------------------------------------

_TINY = dict(fl_overrides={"num_clients": 6, "participation": 1.0,
                           "num_rounds": 8, "local_batch_size": 4},
             lora_overrides={"rank_levels": (4, 8), "rank_probs": (0.5, 0.5)},
             num_classes=4, d_model=32, samples_per_class=8,
             batches_per_round=1)


def _run(engine, mode, rounds=3, **kw):
    exp = build_experiment("raflora", round_engine=engine,
                           transport=TransportConfig(mode=mode), **_TINY,
                           **kw)
    exp.server.run(rounds)
    if engine == "async":
        exp.server.drain_pending()
    return exp


def _adapter_products(server):
    """{adapter path: lora_b @ lora_a}: the SVD realloc's sign/rotation
    freedom cancels in the product (b_g = U sqrt(S), a_g = sqrt(S) V^T),
    so products -- unlike raw factors -- compare across runs."""
    flat = jax.tree_util.tree_flatten_with_path(server.global_lora)[0]
    d = {tuple(str(getattr(p, "key", p)) for p in path): np.asarray(leaf)
         for path, leaf in flat}
    keys = sorted({k[:-1] for k in d if k[-1] == "lora_b"})
    return {k: d[k + ("lora_b",)] @ d[k + ("lora_a",)] for k in keys}


class TestEngineMatrix:
    @pytest.mark.parametrize("mode", ["int8", "bf16"])
    def test_sequential_equals_batched(self, mode):
        """Same encode order, same quantized bytes, same aggregation, ONE
        round from identical state: engine equivalence is a per-round
        property -- over several rounds the truncated SVD's noise tail
        amplifies f32 op-order differences chaotically (over 3 rounds, 7 of
        2048 int8 product entries missed by 4.4e-4). int8 rounding is a
        step function, so the engines' differing f32 op order (per-client
        loop vs stacked vmap) could still flip a quantization decision
        within the round. atol 2e-4 is ~3% of the largest product entry
        (~6e-3 here): room for such a flip, far above one round's measured
        difference (~5e-9). Compared on effective PRODUCTS
        (sign/rotation-invariant)."""
        seq = _run("sequential", mode, rounds=1)
        bat = _run("batched", mode, rounds=1)
        np.testing.assert_allclose(seq.server.energy.higher_rank_ratio,
                                   bat.server.energy.higher_rank_ratio,
                                   rtol=5e-3, atol=5e-4)
        ps, pb = _adapter_products(seq.server), _adapter_products(bat.server)
        assert sorted(ps) == sorted(pb)
        for k in ps:
            np.testing.assert_allclose(ps[k], pb[k], atol=2e-4,
                                       err_msg=str(k))

    def test_sharded_tracks_batched(self):
        """The quantized psum collective folds scale*sqrt(omega) into one
        column vector (one fewer f32 round-trip than the local path), so
        agreement is to f32-association tolerance, not bit-exact."""
        from repro.launch.mesh import make_fl_mesh
        bat = _run("batched", "int8", rounds=2)
        shd = _run("sharded", "int8", rounds=2,
                   mesh=make_fl_mesh(jax.device_count()))
        np.testing.assert_allclose(shd.server.energy.higher_rank_ratio,
                                   bat.server.energy.higher_rank_ratio,
                                   rtol=5e-3, atol=5e-4)

    @pytest.mark.parametrize("engine", ["async", "event"])
    def test_buffered_engines_run_and_accumulate(self, engine):
        """Async/event engines trigger at their own cadence (different
        cohort compositions than the sync engines -- no trace equality to
        assert), but compression must leave them healthy: finite energies,
        rounds recorded, and error-feedback state for every participant."""
        kw = {}
        if engine == "async":
            exp = _run("async", "int8", rounds=4, pipeline_depth=2,
                       staleness_gamma=0.8)
        else:
            from repro.federation.events import (EventScheduler,
                                                 standard_trigger,
                                                 standard_straggler_latency)
            exp = build_experiment(
                "raflora", round_engine="async",
                transport=TransportConfig(mode="int8"), **_TINY)
            exp.server.set_event_scheduler(EventScheduler(
                standard_straggler_latency(0.5), standard_trigger("count", 6),
                round_interval=1.0))
            exp.server.run(4)
            exp.server.drain_pending()
        assert len(exp.server.history) >= 2
        assert np.isfinite(exp.server.energy.higher_rank_ratio).all()
        state = exp.server.transport.state_arrays()
        assert state, "error-feedback accumulators must be non-empty"
        assert all(v.dtype == np.float32 for v in state.values())


# ---------------------------------------------------------------------------
# checkpointing: mid-buffer resume + pre-transport back-compat
# ---------------------------------------------------------------------------


def _async_exp():
    return build_experiment("raflora", round_engine="async",
                            pipeline_depth=2, staleness_gamma=0.8,
                            transport=TransportConfig(mode="int8"), **_TINY)


class TestTransportCheckpoint:
    def test_mid_buffer_resume_equals_uninterrupted(self, tmp_path):
        """Save mid-buffer (pending client updates in flight, error-feedback
        accumulators non-empty), restore into a fresh server, continue:
        the resumed run must equal the uninterrupted one."""
        full = _async_exp()
        full.server.run(5)
        full.server.drain_pending()

        part = _async_exp()
        part.server.run(3)
        assert part.server._pending, "must save mid-buffer"
        assert part.server.transport.has_state(), \
            "accumulators must be non-empty at save time"
        path = str(tmp_path / "tx_ckpt")
        part.server.save(path)

        resumed = _async_exp()
        resumed.server.restore(path)
        # accumulators round-trip bit-exactly
        want = part.server.transport.state_arrays()
        got = resumed.server.transport.state_arrays()
        assert sorted(want) == sorted(got)
        for k in want:
            np.testing.assert_array_equal(want[k], got[k])
        resumed.server.run(2)
        resumed.server.drain_pending()

        for sf, sr in zip(full.server.history, resumed.server.history):
            assert sf.clients == sr.clients and sf.ranks == sr.ranks
            np.testing.assert_allclose(sf.mean_client_loss,
                                       sr.mean_client_loss, rtol=1e-6)
        np.testing.assert_allclose(full.server.energy.higher_rank_ratio,
                                   resumed.server.energy.higher_rank_ratio,
                                   rtol=1e-5, atol=1e-6)
        for a, b in zip(jax.tree.leaves(full.server.global_lora),
                        jax.tree.leaves(resumed.server.global_lora)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)

    def test_pre_transport_checkpoint_restores_with_warning(self, tmp_path):
        """Back-compat (bugfix satellite): a checkpoint written BEFORE the
        transport existed has no accumulator sidecar -- restore() must not
        KeyError; accumulators zero-init with a warning."""
        old = build_experiment("raflora", round_engine="batched", **_TINY)
        old.server.run(2)
        path = str(tmp_path / "pre_transport")
        old.server.save(path)

        new = build_experiment("raflora", round_engine="batched",
                               transport=TransportConfig(mode="int8"),
                               **_TINY)
        with pytest.warns(RuntimeWarning,
                          match="predates the compressed update transport"):
            new.server.restore(path)
        assert not new.server.transport.has_state()
        new.server.run(1)          # zero-init accumulators: training resumes
        assert new.server.transport.has_state()

    def test_quantized_pending_plans_roundtrip(self, tmp_path):
        """The async pending buffer may hold QUANTIZED factor pairs; the
        plan (de)serialization must preserve payload dtype + scales."""
        part = _async_exp()
        part.server.run(3)
        assert part.server._pending

        def quant_leaves(plans):
            out = {}
            for plan in plans:
                for gi, (members, r_max, factors) in \
                        enumerate(plan.group_factors):
                    for parent, val in factors.items():
                        if is_quantized(val[0]):
                            out[(plan.round, gi, parent)] = val
            return out

        old_leaves = quant_leaves(part.server._pending)
        assert old_leaves, "pending buffer must hold quantized factors"
        path = str(tmp_path / "pending")
        part.server.save(path)
        resumed = _async_exp()
        resumed.server.restore(path)
        new_leaves = quant_leaves(resumed.server._pending)
        assert sorted(old_leaves) == sorted(new_leaves)
        for key, (ob, oa) in old_leaves.items():
            for old, new in zip((ob, oa), new_leaves[key]):
                assert is_quantized(new)
                assert np.asarray(new.q).dtype == np.asarray(old.q).dtype
                np.testing.assert_array_equal(np.asarray(old.q),
                                              np.asarray(new.q))
                np.testing.assert_array_equal(np.asarray(old.scale),
                                              np.asarray(new.scale))


# ---------------------------------------------------------------------------
# transport state machinery
# ---------------------------------------------------------------------------


class TestUpdateTransportState:
    def test_state_roundtrip_and_ghost_discard(self):
        tr = UpdateTransport(TransportConfig(mode="int8"))
        rng = np.random.default_rng(1)
        b = rng.normal(size=(3, 8, 4)).astype(np.float32)
        a = rng.normal(size=(3, 4, 8)).astype(np.float32)
        out = tr.encode_group([5, -1, 9],
                              {("L",): (jnp.asarray(b), jnp.asarray(a))})
        assert is_quantized(out[("L",)][0])
        state = tr.state_arrays()
        assert set(state) == {"c5/L/b", "c5/L/a", "c9/L/b", "c9/L/a"}
        tr2 = UpdateTransport(TransportConfig(mode="int8"))
        tr2.load_state_arrays(state)
        for k, v in tr2.state_arrays().items():
            np.testing.assert_array_equal(v, state[k])

    def test_magnitudes_pass_through(self):
        tr = UpdateTransport(TransportConfig(mode="int8"))
        m = jnp.ones((7,))
        out = tr.encode_client(0, {(("proj",), "m"): m})
        assert out[(("proj",), "m")] is m

    def test_payload_bytes(self):
        tr8 = UpdateTransport(TransportConfig(mode="int8"))
        tr16 = UpdateTransport(TransportConfig(mode="bf16"))
        d, n, r = 64, 64, 8
        f32 = (d * r + r * n) * 4
        assert tr8.payload_bytes(d, n, r) < f32 / 3
        assert tr16.payload_bytes(d, n, r) < f32 / 1.9
