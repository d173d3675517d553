"""MPMD pipeline parallelism + ZeRO sharded update tests (ISSUE 14).

Tier-1-safe coverage: the 1F1B schedule's invariants, the ZeRO/replicated
bit-parity and dp x memory contract, the activation-transport rungs, the
per-stage checkpoint layout + reshard-across-dp restore, and the acceptance
PARITY GATE — MPMD pipeline vs single-jit GPipe vs unpipelined single
program, same init/batch, losses and grad norms allclose on the CPU mesh.

The `chaos`+`cluster` test SIGKILLs a stage-gang member mid-step and
asserts the supervisor aborts the mesh, the pipeline reshapes, and stage
shards restore with a continuous step counter (extends the
test_train_elastic patterns to the MPMD path).
"""

import os
import signal
import threading
import time

import numpy as np
import pytest

from ray_tpu.train.mpmd import (
    build_1f1b,
    build_interleaved_1f1b,
    max_in_flight,
    make_local_comms,
    run_local_pipeline,
    theoretical_bubble_fraction,
    ReplicatedAdamW,
    ShardedAdamW,
    SoloComm,
    WireCodec,
)
from ray_tpu.train.mpmd.schedule import B, F


# --------------------------------------------------------------------------
# 1F1B schedule invariants (no jax)
# --------------------------------------------------------------------------
class TestSchedule:
    @pytest.mark.parametrize("S,M", [(1, 1), (2, 2), (2, 4), (3, 3), (4, 8), (5, 2)])
    def test_every_microbatch_once_and_ordered(self, S, M):
        for s in range(S):
            ops = build_1f1b(s, S, M)
            fwd = [i for op, i in ops if op == F]
            bwd = [i for op, i in ops if op == B]
            assert fwd == list(range(M)) and bwd == list(range(M))
            # B_i strictly after F_i.
            for i in range(M):
                assert ops.index((F, i)) < ops.index((B, i))

    @pytest.mark.parametrize("S,M", [(2, 4), (3, 6), (4, 8)])
    def test_in_flight_bound(self, S, M):
        """The saved-activation window never exceeds min(M, S - s) — the
        1F1B memory bound that motivates the schedule over GPipe."""
        for s in range(S):
            live = 0
            peak = 0
            for op, _ in build_1f1b(s, S, M):
                live += 1 if op == F else -1
                peak = max(peak, live)
            assert peak == max_in_flight(s, S, M)

    def test_theoretical_bubble(self):
        assert theoretical_bubble_fraction(1, 4) == 0.0
        assert theoretical_bubble_fraction(4, 4) == pytest.approx(3 / 7)
        # Interleaving divides the fill/drain cost by v.
        assert theoretical_bubble_fraction(2, 4) == pytest.approx(1 / 5)
        assert theoretical_bubble_fraction(2, 4, 2) == pytest.approx(1 / 9)
        assert theoretical_bubble_fraction(4, 8, 4) == pytest.approx(3 / 35)

    def test_reshape_dp_picker_respects_batch_divisibility(self):
        """Reshapes only pick dp values that divide the band ceiling — the
        batch contract (B % (dp_max * M) == 0) only guarantees even shards
        for those; dp=3 in a [1, 4] band would crash the step loop."""
        from ray_tpu.train.mpmd.trainer import MPMDTrainer

        pick = MPMDTrainer._pick_dp
        assert [pick(f, 1, 4) for f in (0, 1, 2, 3, 4, 9)] == [1, 1, 2, 2, 4, 4]
        assert pick(3, 2, 4) == 2
        # Band with no feasible divisor: the smallest candidate is returned
        # (spawn fails honestly, consuming restart budget — no deadlock).
        assert pick(1, 3, 4) == 4


# --------------------------------------------------------------------------
# Interleaved (virtual-stage) 1F1B schedule invariants (no jax)
# --------------------------------------------------------------------------
def _simulate_depth1(S, M, v):
    """Run every stage's op list against depth-1 blocking channels (the
    compiled-DAG contract: a write blocks until the reader drained the
    previous message). Single-threaded round-robin: repeatedly scan for a
    stage whose next op can run; if no stage can make progress before all
    lists drain, that IS a deadlock — exactly what would wedge the real
    pipeline. Returns per-stage peak in-flight forward count."""
    P = S * v
    lists = {s: build_interleaved_1f1b(s, S, M, v) for s in range(S)}
    pc = {s: 0 for s in range(S)}
    chan: dict = {}  # (kind, from_vs, to_vs) -> messages in flight
    live = {s: 0 for s in range(S)}
    peak = {s: 0 for s in range(S)}

    def vs_of(s, c):
        return c * S + s

    def can_run(s):
        if pc[s] >= len(lists[s]):
            return False
        op, _, c = lists[s][pc[s]]
        vs = vs_of(s, c)
        kind = "a" if op == F else "g"
        src = vs - 1 if op == F else vs + 1
        need_recv = (vs > 0) if op == F else (vs < P - 1)
        dst = (vs + 1 if vs < P - 1 else None) if op == F else (
            vs - 1 if vs > 0 else None)
        if need_recv and chan.get((kind, src, vs), 0) < 1:
            return False
        if dst is not None and chan.get((kind, vs, dst), 0) >= 1:
            return False
        return True

    def run(s):
        op, _, c = lists[s][pc[s]]
        vs = vs_of(s, c)
        kind = "a" if op == F else "g"
        if op == F:
            if vs > 0:
                chan[(kind, vs - 1, vs)] -= 1
            if vs < P - 1:
                chan[(kind, vs, vs + 1)] = chan.get((kind, vs, vs + 1), 0) + 1
            live[s] += 1
            peak[s] = max(peak[s], live[s])
        else:
            if vs < P - 1:
                chan[(kind, vs + 1, vs)] -= 1
            if vs > 0:
                chan[(kind, vs, vs - 1)] = chan.get((kind, vs, vs - 1), 0) + 1
            live[s] -= 1
        pc[s] += 1

    while any(pc[s] < len(lists[s]) for s in range(S)):
        ran = False
        for s in range(S):
            while can_run(s):
                run(s)
                ran = True
        if not ran:
            stuck = {s: lists[s][pc[s]] for s in range(S)
                     if pc[s] < len(lists[s])}
            raise AssertionError(f"deadlock: stages stuck at {stuck}")
    return peak


# The acceptance grid: every (S, v) pairing the bench shapes use, plus the
# deeper pipes that stress the warmup formula.
_INTERLEAVE_GRID = [
    (S, M, v)
    for S in (2, 3, 4, 5)
    for v in (2, 3, 4)
    for M in (S, 2 * S, 4 * S)
]


class TestInterleavedSchedule:
    @pytest.mark.parametrize("S,M", [(1, 1), (2, 2), (2, 4), (3, 6), (4, 8)])
    def test_v1_reproduces_build_1f1b(self, S, M):
        """num_chunks=1 must be EXACTLY the proven flat schedule with a
        zero chunk index appended — no behavioural drift for existing
        configs or their checkpoints."""
        for s in range(S):
            want = [(op, i, 0) for op, i in build_1f1b(s, S, M)]
            assert build_interleaved_1f1b(s, S, M, 1) == want

    @pytest.mark.parametrize("S,M,v", _INTERLEAVE_GRID)
    def test_completeness_and_order(self, S, M, v):
        """Each stage runs F and B exactly once per (microbatch, chunk),
        forwards in virtual-stage wave order, and B_(i,c) after F_(i,c)."""
        for s in range(S):
            ops = build_interleaved_1f1b(s, S, M, v)
            fwd = [(i, c) for op, i, c in ops if op == F]
            bwd = [(i, c) for op, i, c in ops if op == B]
            every = {(i, c) for i in range(M) for c in range(v)}
            assert len(ops) == 2 * M * v
            assert set(fwd) == every and set(bwd) == every
            assert len(set(fwd)) == len(fwd) and len(set(bwd)) == len(bwd)
            for key in every:
                assert ops.index((F, *key)) < ops.index((B, *key))

    @pytest.mark.parametrize("S,M,v", _INTERLEAVE_GRID)
    def test_deadlock_free_on_depth1_channels(self, S, M, v):
        """The whole point of the per-stage op-list proof style: all S
        lists, executed against depth-1 blocking channels, drain without a
        stall cycle. This simulation IS the proof for each grid point."""
        _simulate_depth1(S, M, v)

    @pytest.mark.parametrize("S,M,v", _INTERLEAVE_GRID)
    def test_in_flight_bound(self, S, M, v):
        """Peak saved-activation count matches max_in_flight exactly — the
        v>1 memory bound the docs advertise (warmup+1, capped at M*v)."""
        peak = _simulate_depth1(S, M, v)
        for s in range(S):
            assert peak[s] == max_in_flight(s, S, M, v), (s, peak)

    def test_expected_op_list_s2_m2_v2(self):
        """Pin one small schedule end-to-end so a refactor that permutes
        ops (while still passing the property tests) is visible in review."""
        assert build_interleaved_1f1b(0, 2, 2, 2) == [
            (F, 0, 0), (F, 1, 0), (F, 0, 1), (F, 1, 1),
            (B, 0, 1), (B, 1, 1), (B, 0, 0), (B, 1, 0),
        ]

    def test_validation(self):
        with pytest.raises(ValueError, match="num_stages > 1"):
            build_interleaved_1f1b(0, 1, 4, 2)
        with pytest.raises(ValueError, match="num_microbatches % num_stages"):
            build_interleaved_1f1b(0, 2, 3, 2)  # M % S != 0
        with pytest.raises(ValueError, match="out of range"):
            build_interleaved_1f1b(2, 2, 4, 2)  # stage out of range


# --------------------------------------------------------------------------
# ZeRO sharded update (no runtime; dp via in-process comms)
# --------------------------------------------------------------------------
def _run_dp(comms, fn):
    """Run fn(comm) on one thread per dp rank; return results in rank
    order; re-raise the first failure."""
    out = [None] * len(comms)
    errs = []

    def target(i):
        try:
            out[i] = fn(comms[i])
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=target, args=(i,)) for i in range(len(comms))]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive(), "dp thread wedged"
    if errs:
        raise errs[0]
    return out


class TestZeroUpdate:
    def test_local_comm_reduce_scatter_all_gather(self):
        comms = make_local_comms(3)
        vecs = [np.arange(10.0, dtype=np.float32) * (r + 1) for r in range(3)]

        def step(comm):
            chunk = comm.reduce_scatter_flat(vecs[comm.rank])
            return comm.all_gather_flat(chunk)

        outs = _run_dp(comms, step)
        want = np.sum(vecs, axis=0)
        for o in outs:
            np.testing.assert_array_equal(o, want)

    def test_sharded_vs_replicated_bit_identical(self):
        """The ZeRO-on vs replicated A/B: same reduced gradients, so
        elementwise adamw makes the parameter trajectories EXACTLY equal —
        optimizer memory (dp x) is the only difference."""
        n, dp, steps = 1001, 4, 5  # odd n: uneven array_split chunks
        rng = np.random.default_rng(0)
        init = rng.standard_normal(n).astype(np.float32)
        grads = [
            [rng.standard_normal(n).astype(np.float32) for _ in range(dp)]
            for _ in range(steps)
        ]

        def run(opt_cls):
            comms = make_local_comms(dp)
            opts = {}

            def worker(comm):
                opt = opt_cls(init, comm, lr=1e-2, weight_decay=0.01)
                opts[comm.rank] = opt
                full = None
                for t in range(steps):
                    full, _ = opt.step(grads[t][comm.rank])
                return full

            outs = _run_dp(comms, worker)
            return outs, opts

        z_outs, z_opts = run(ShardedAdamW)
        r_outs, r_opts = run(ReplicatedAdamW)
        for zo, ro in zip(z_outs, r_outs):
            assert np.array_equal(zo, ro), "ZeRO diverged from replicated"
        # Every replica converged to the same parameters.
        for o in z_outs[1:]:
            assert np.array_equal(o, z_outs[0])
        # dp x optimizer-memory cut (within array_split rounding).
        zb = sum(z_opts[r].optimizer_bytes for r in range(dp))
        rb = r_opts[0].optimizer_bytes
        assert rb == 3 * n * 4
        assert zb == rb, "sharded state must cover the space exactly once"
        assert max(
            z_opts[r].optimizer_bytes for r in range(dp)
        ) <= rb / dp + 3 * 4  # one extra element per uneven chunk

    def test_solo_comm_matches_dp1(self):
        n = 64
        init = np.ones(n, np.float32)
        g = np.full(n, 0.5, np.float32)
        a = ShardedAdamW(init, SoloComm(), lr=1e-2)
        b = ReplicatedAdamW(init, SoloComm(), lr=1e-2)
        fa, _ = a.step(g)
        fb, _ = b.step(g)
        assert np.array_equal(fa, fb)

    def test_reshard_restore_across_dp_change(self, tmp_path):
        """Stage-local ZeRO shards written at dp=2 restore at dp=1 through
        the elastic per-stage layout: the axis-0 reshard hands the new rank
        exactly the concatenation of the old chunks (bitwise)."""
        from ray_tpu.train.elastic import (
            AsyncShardWriter,
            ShardedCheckpoint,
            stage_root,
        )
        from ray_tpu.train.elastic.state import ElasticState

        n, dp = 37, 2
        rng = np.random.default_rng(1)
        init = rng.standard_normal(n).astype(np.float32)
        comms = make_local_comms(dp)
        opts = {}

        def worker(comm):
            opt = ShardedAdamW(init, comm, lr=1e-2)
            opts[comm.rank] = opt
            for t in range(3):
                opt.step(rng.standard_normal(n).astype(np.float32) * 0)
            return opt.ckpt_tree()

        trees = _run_dp(comms, worker)
        root = stage_root(str(tmp_path), 0)
        writers = [
            AsyncShardWriter(root, r, dp, gen="g1", mode="sharded")
            for r in range(dp)
        ]
        for r, w in enumerate(writers):
            st = ElasticState(step=3)
            st.record_pipeline(stage=0, num_stages=2)
            st.extra["opt_t"] = 3
            w.save(3, trees[r], st)
        assert all(w.flush() for w in writers)
        for w in writers:
            w.close()

        state, tree = ShardedCheckpoint.restore(root, 0, 1, step=3)
        state.check_pipeline(0, 2)
        with pytest.raises(ValueError, match="stage splits"):
            state.check_pipeline(1, 2)
        new_opt = ShardedAdamW(init, SoloComm(), lr=1e-2)
        new_opt.load_ckpt_tree(tree, t=state.extra["opt_t"])
        for name in ("master", "m", "v"):
            want = np.concatenate([np.asarray(t[name]) for t in trees])
            np.testing.assert_array_equal(getattr(new_opt, name), want)


# --------------------------------------------------------------------------
# Per-stage checkpoint layout (pure fs)
# --------------------------------------------------------------------------
class TestStageCheckpointLayout:
    def test_latest_common_committed(self, tmp_path):
        from ray_tpu.train.elastic import (
            AsyncShardWriter,
            latest_common_committed,
            stage_root,
        )
        from ray_tpu.train.elastic.state import ElasticState

        root = str(tmp_path)
        assert latest_common_committed(root, 2) is None
        writers = [
            AsyncShardWriter(stage_root(root, s), 0, 1, gen="g")
            for s in range(2)
        ]
        for s, w in enumerate(writers):
            w.save(1, {"x": np.zeros(2)}, ElasticState(step=1))
            assert w.flush()
        step, dirs = latest_common_committed(root, 2)
        assert step == 1 and len(dirs) == 2
        # Step 2 commits only on stage 0 (stage 1 "crashed" mid-save): the
        # pipeline's restore point stays 1.
        writers[0].save(2, {"x": np.ones(2)}, ElasticState(step=2))
        assert writers[0].flush()
        assert latest_common_committed(root, 2)[0] == 1
        for w in writers:
            w.close()


# --------------------------------------------------------------------------
# Parity gate: MPMD vs single-jit GPipe vs unpipelined (acceptance)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_model():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    cfg = gpt.GPTConfig(
        vocab_size=128, n_layers=4, d_model=32, n_heads=2, d_head=16,
        d_mlp=64, max_seq=16, dtype=jnp.float32, attn_impl="ref",
        remat=False, tie_embeddings=False,
    )
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    batches = [rng.integers(0, cfg.vocab_size, (8, 9)) for _ in range(2)]
    return cfg, params, batches


class TestParityGate:
    def _reference(self, cfg, params, batches):
        """Unpipelined single program with the same adamw."""
        import jax

        from ray_tpu.collective.ops import zero_flatten, zero_unflatten
        from ray_tpu.models import gpt

        flat, spec = zero_flatten(jax.tree_util.tree_map(np.asarray, params))
        opt = ReplicatedAdamW(flat, SoloComm(), lr=1e-3)
        p, losses, gnorms, grads_list = params, [], [], []
        for batch in batches:
            bt = {"tokens": np.asarray(batch)}
            loss, grads = jax.value_and_grad(
                lambda q: gpt.loss_fn(q, bt, cfg)
            )(p)
            losses.append(float(loss))
            gnorms.append(float(gpt.optax_global_norm(grads)))
            grads_list.append(jax.tree_util.tree_map(np.asarray, grads))
            gflat, _ = zero_flatten(grads_list[-1])
            new_flat, _ = opt.step(gflat)
            p = zero_unflatten(new_flat, spec)
        return p, losses, gnorms, grads_list

    @pytest.mark.parametrize("S,dp,M", [(2, 2, 2), (2, 1, 4)])
    def test_mpmd_matches_unpipelined(self, tiny_model, S, dp, M):
        cfg, params, batches = tiny_model
        ref_p, ref_losses, ref_gnorms, _ = self._reference(cfg, params, batches)
        out = run_local_pipeline(cfg, S, dp, M, batches, params=params, lr=1e-3)
        np.testing.assert_allclose(
            [h["loss"] for h in out["history"]], ref_losses,
            rtol=2e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            [h["grad_norm"] for h in out["history"]], ref_gnorms,
            rtol=2e-4, atol=1e-5,
        )
        for k, v in out["params"].items():
            np.testing.assert_allclose(
                v, np.asarray(ref_p[k]), rtol=1e-4, atol=1e-5, err_msg=k
            )

    def test_mpmd_matches_single_jit_gpipe(self, tiny_model):
        """Same init/batch: the MPMD host-scheduled pipeline and the in-jit
        GPipe program agree on loss AND gradients (GPipe itself is
        validated against serial in test_pipeline.py; this closes the
        triangle)."""
        import jax

        from ray_tpu.models import gpt
        from ray_tpu.parallel import MeshSpec

        cfg, params, batches = tiny_model
        batch = {"tokens": np.asarray(batches[0])}
        mesh = MeshSpec(pp=2).build(jax.devices()[:2])
        staged = gpt.split_stage_params(params, cfg, 2)
        gpipe_loss, gpipe_grads = jax.jit(
            jax.value_and_grad(
                lambda p: gpt.pipeline_loss_fn(p, batch, cfg, mesh, 2)
            )
        )(staged)
        gpipe_grads = gpt.merge_stage_params(gpipe_grads, cfg)
        gpipe_gnorm = float(gpt.optax_global_norm(gpipe_grads))

        out = run_local_pipeline(cfg, 2, 1, 2, batches[:1], params=params, lr=1e-3)
        h = out["history"][0]
        np.testing.assert_allclose(h["loss"], float(gpipe_loss), rtol=2e-3)
        np.testing.assert_allclose(h["grad_norm"], gpipe_gnorm, rtol=2e-2)

    def test_zero_on_off_bit_identical_params(self, tiny_model):
        """ZeRO-on vs replicated through the REAL pipeline runners: final
        parameters bit-identical after N steps, optimizer bytes ~dp x
        apart (the acceptance memory claim)."""
        cfg, params, batches = tiny_model
        out_z = run_local_pipeline(
            cfg, 2, 2, 2, batches, params=params, zero=True, lr=1e-3
        )
        out_r = run_local_pipeline(
            cfg, 2, 2, 2, batches, params=params, zero=False, lr=1e-3
        )
        for k in out_z["params"]:
            assert np.array_equal(out_z["params"][k], out_r["params"][k]), k
        zb = out_z["history"][-1]["opt_bytes_per_replica"]
        rb = out_r["history"][-1]["opt_bytes_per_replica"]
        assert 1.9 < rb / zb < 2.1  # dp = 2

    @pytest.mark.parametrize("M", [2, 4])
    def test_interleaved_matches_unpipelined_and_v1(self, tiny_model, M):
        """The tentpole parity gate: v=2 with the f32 wire is the SAME
        model as v=1 — losses, grad norms, and final params all allclose
        against both the unpipelined reference and the proven v=1
        pipeline (4 layers split into 2*2 virtual stages). The chunked
        jit programs fuse differently, so parity is allclose, not
        bitwise, and v=2 is held to v=1 at the tolerances that hold both
        to the reference. Read on the CPU (PR 30), M=2: the losses differ
        by half a float32 ulp in the second step (4.8698375 against
        4.8698378, 4.9e-8 relative) and one element of 384 of `b_qkv`
        ends 1.145e-6 apart (-1.1e-7 against 1.03e-6; the reference has
        6.9e-7, and v=1 itself is up to 7.8e-7 from it). It is a K bias:
        softmax ignores a constant added to every key, so its gradient
        is zero but for rounding (-4.7e-12, -1.1e-13 in the reference)
        and adamw divides that noise by sqrt(v) + 1e-8. M=4 read 6.8e-7
        on another such element. An absolute 1e-6 between two float32
        programs was under that noise; 1e-5 holds them as it holds each
        to the reference."""
        cfg, params, batches = tiny_model
        ref_p, ref_losses, ref_gnorms, _ = self._reference(cfg, params, batches)
        out1 = run_local_pipeline(cfg, 2, 1, M, batches, params=params, lr=1e-3)
        outv = run_local_pipeline(
            cfg, 2, 1, M, batches, params=params, lr=1e-3, num_chunks=2
        )
        np.testing.assert_allclose(
            [h["loss"] for h in outv["history"]], ref_losses,
            rtol=2e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            [h["grad_norm"] for h in outv["history"]], ref_gnorms,
            rtol=2e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            [h["loss"] for h in outv["history"]],
            [h["loss"] for h in out1["history"]],
            rtol=2e-5, atol=1e-6,
        )
        for k, val in outv["params"].items():
            np.testing.assert_allclose(
                val, np.asarray(ref_p[k]), rtol=1e-4, atol=1e-5, err_msg=k
            )
            np.testing.assert_allclose(
                val, out1["params"][k], rtol=1e-4, atol=1e-5, err_msg=k
            )

    def test_bf16_wire_loss_curve(self, tiny_model):
        """The bf16 wire gate: activations/grads cross hops in bf16 (master
        weights and the update stay f32) — the loss curve tracks the f32
        wire within bf16's ~3 decimal digits (rtol 2e-2 documented in
        docs/MPMD_TRAINING.md), and the codec ships exactly half the
        bytes."""
        cfg, params, batches = tiny_model
        f32 = run_local_pipeline(cfg, 2, 1, 2, batches, params=params, lr=1e-3)
        bf16 = run_local_pipeline(
            cfg, 2, 1, 2, batches, params=params, lr=1e-3, wire_dtype="bf16"
        )
        np.testing.assert_allclose(
            [h["loss"] for h in bf16["history"]],
            [h["loss"] for h in f32["history"]],
            rtol=2e-2,
        )
        ws = bf16["wire_stats"]
        assert ws["frames"] > 0
        assert ws["wire_bytes"] * 2 == ws["raw_bytes"]
        # f32 is the identity codec — bit-exact parity mode.
        assert f32["wire_stats"]["wire_bytes"] == f32["wire_stats"]["raw_bytes"]

    def test_wire_codec_round_trip(self):
        rng = np.random.default_rng(3)
        arr = rng.standard_normal((7, 5)).astype(np.float32)
        ident = WireCodec("f32")
        w, meta = ident.encode(arr)
        assert w is arr and meta is None
        bf = WireCodec("bf16")
        w, meta = bf.encode(arr)
        assert w.dtype == np.uint16 and w.nbytes == arr.nbytes // 2
        back = bf.decode(w, meta)
        assert back.dtype == np.float32
        np.testing.assert_allclose(back, arr, rtol=8e-3, atol=1e-6)
        with pytest.raises(ValueError, match="wire_dtype"):
            WireCodec("fp8")

    def test_tied_embedding_bridge_parity(self):
        """Tied embeddings through the pipeline: the first/last-stage
        gradient bridge makes the split model track the unpipelined tied
        reference, and the two tok_embed copies stay BIT-identical (both
        hosts sum the same two partials — float addition commutes)."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import gpt

        cfg = gpt.GPTConfig(
            vocab_size=128, n_layers=4, d_model=32, n_heads=2, d_head=16,
            d_mlp=64, max_seq=16, dtype=jnp.float32, attn_impl="ref",
            remat=False, tie_embeddings=True,
        )
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        batches = [rng.integers(0, cfg.vocab_size, (8, 9)) for _ in range(2)]
        ref_p, ref_losses, _, _ = self._reference(cfg, params, batches)
        out = run_local_pipeline(cfg, 2, 1, 2, batches, params=params, lr=1e-3)
        # Losses + params only: grad_norm double-counts tok_embed (it
        # appears in both boundary stages' accumulators by design).
        np.testing.assert_allclose(
            [h["loss"] for h in out["history"]], ref_losses,
            rtol=2e-5, atol=1e-6,
        )
        for k, val in out["params"].items():
            np.testing.assert_allclose(
                val, np.asarray(ref_p[k]), rtol=1e-4, atol=1e-5, err_msg=k
            )
        te0 = out["runners"][0][0].chunk_params_host(0)["tok_embed"]
        te1 = out["runners"][1][0].chunk_params_host(0)["tok_embed"]
        assert np.array_equal(te0, te1), "bridge copies diverged"

    def test_partitionable_checks(self):
        from ray_tpu.models import gpt

        # Tied embeddings are now ALLOWED (the bridge handles them).
        gpt.check_mpmd_partitionable(gpt.gpt2_small(), 2)
        # MoE still rejected: stage-local aux loss would be silently wrong.
        moe = gpt.gpt2_small(mlp_type="moe")
        with pytest.raises(NotImplementedError, match="aux loss"):
            gpt.check_mpmd_partitionable(moe, 2)
        # Interleaving needs a real ring and even layer division.
        cfg = gpt.gpt2_small()
        with pytest.raises(ValueError, match="num_stages > 1"):
            gpt.check_mpmd_partitionable(cfg, 1, num_chunks=2)
        with pytest.raises(ValueError, match="not divisible"):
            gpt.check_mpmd_partitionable(cfg, 5, num_chunks=2)  # 12 % 10


# --------------------------------------------------------------------------
# Activation transport rungs (cluster runtime: arena + object_sources)
# --------------------------------------------------------------------------
@pytest.mark.cluster
class TestActTransport:
    def test_arena_and_span_rungs(self, cluster_runtime):
        from ray_tpu.train.mpmd.transport import ActTransport

        t = ActTransport(inline_max_bytes=0, timeout_s=30)
        arr = np.arange(100_000, dtype=np.float32)  # 400 KB > thresholds
        desc, pin = t.publish(arr)
        assert pin is not None and desc["span"] is not None
        # Rung 2: same-node shared-store read.
        got = t.fetch(desc)
        np.testing.assert_array_equal(got, arr)
        assert t.stats["fetch_local"] == 1
        # Rung 3: span pull over the bulk wire (simulate a remote consumer
        # by withholding the local name).
        got2 = t.fetch({**desc, "name": None})
        np.testing.assert_array_equal(got2, arr)
        assert t.stats["fetch_span"] == 1
        # Small tensors stay inline regardless of inline_max: the store
        # would land them on the inline plane where no rung can read them.
        desc3, pin3 = t.publish(np.arange(16, dtype=np.float32))
        assert "inline" in desc3 and pin3 is None
        del pin
        # Rung exhaustion is loud, not a wedge.
        with pytest.raises(RuntimeError, match="unreachable"):
            t.fetch({"hex": "0" * 28, "name": None, "span": (0, 4),
                     "dtype": "<f4", "shape": (1,)})


# --------------------------------------------------------------------------
# Chaos acceptance: SIGKILL a stage-gang member mid-step (MPMD path)
# --------------------------------------------------------------------------
@pytest.mark.chaos
@pytest.mark.cluster
def test_sigkill_stage_member_reshapes_and_resumes(tmp_path):
    """SIGKILL one stage-gang replica mid-step: the supervisor aborts the
    whole mesh within its deadline (stage collective groups interrupted, no
    wedged barrier), the pipeline reshapes (dp re-picked from feasible
    capacity within the band), stage-local shards restore from the last
    COMMON committed checkpoint, and the step counter continues to the
    configured total."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu.core import api
    from ray_tpu.models import gpt
    from ray_tpu.train import FailureConfig, RunConfig
    from ray_tpu.train.elastic import latest_common_committed
    from ray_tpu.train.mpmd import MPMDOptions, MPMDTrainer

    cfg = gpt.GPTConfig(
        vocab_size=128, n_layers=2, d_model=32, n_heads=2, d_head=16,
        d_mlp=64, max_seq=16, dtype=jnp.float32, attn_impl="ref",
        remat=False, tie_embeddings=False,
    )
    total = 8

    def batch_fn(step):
        return np.random.default_rng(step).integers(0, 128, (8, 9))

    ray_tpu.init(num_cpus=4)
    try:
        trainer = MPMDTrainer(
            cfg,
            MPMDOptions(
                num_stages=2, dp=2, dp_min=1, dp_max=2, num_microbatches=2,
                zero=True, step_timeout_s=60, ckpt_every=1,
            ),
            total_steps=total,
            batch_fn=batch_fn,
            run_config=RunConfig(
                storage_path=str(tmp_path),
                failure_config=FailureConfig(
                    max_failures=2, backoff_base_s=0.25,
                ),
            ),
        )
        killed = {}

        def killer():
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline:
                found = latest_common_committed(
                    trainer.run_config.resolve_storage(), 2
                )
                if found and found[0] >= 2 and trainer.gang is not None:
                    break
                time.sleep(0.05)
            gang = trainer.gang
            if gang is None:
                return
            victim = gang.actors[(1, 0)]
            try:
                pid = api.get(victim.pid.remote(), timeout=10)
            except Exception:  # noqa: BLE001
                return
            os.kill(pid, signal.SIGKILL)
            killed["pid"] = pid
            killed["t"] = time.monotonic()

        th = threading.Thread(target=killer, daemon=True)
        th.start()
        res = trainer.fit()
        t_done = time.monotonic()
        sup = trainer._supervisor

        assert killed.get("pid"), "killer thread never fired"
        assert res["error"] is None, res["error"]
        assert res["attempts"] >= 1, "the gang never restarted"
        # Abort + reshape + restore happened promptly — nobody waited out
        # a 300s collective round on the dead peer.
        assert sup.last_recovery_s is not None and sup.last_recovery_s < 60
        assert t_done - killed["t"] < 90
        # Reshaped dp stays inside the band.
        assert 1 <= res["dp"] <= 2
        # Step counter continuous to the end (re-runs of the steps after
        # the last commit are legitimate; gaps are not).
        steps = sorted({h["step"] for h in res["history"]})
        assert steps == list(range(1, total + 1)), steps
        # Deterministic resume: re-run steps report identical losses.
        by_step = {}
        for h in res["history"]:
            by_step.setdefault(h["step"], []).append(h["loss"])
        for step, losses in by_step.items():
            for x in losses[1:]:
                assert x == pytest.approx(losses[0], rel=1e-5), (
                    f"step {step} diverged across incarnations: {losses}"
                )
    finally:
        ray_tpu.shutdown()
