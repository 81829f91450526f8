"""Million-client cohort substrate tests (fedml_tpu/scale/ — ISSUE 6).

Pins the subsystem's contracts:

1. **Registry**: packed-column round-trip (save/load), sampling
   determinism under a fixed seed (across instances and processes-worth of
   rebuilds), weighted-sampling bias, participation/staleness accounting,
   ledger identity digests.
2. **Prefetcher**: the stream never blocks the round beyond its own data
   (cold takes work), never serves a stale shard (wrong-cohort takes are
   misses, and a prefetching run is BITWISE equal to a synchronous one),
   and overlap is measured.
3. **Partition rules**: regex→PartitionSpec resolution fixtures including
   rule precedence, scalar exemption, the no-match fallback, and the
   parse syntax; rule-driven mesh sharding reproduces the legacy
   hard-coded first-axis sharding bitwise over the model zoo.
4. **Recompile-safety**: steady-state registry rounds trigger ZERO XLA
   compiles (cohort resampling can never be a recompile source).
5. **Crash-safety**: a registry-backed run preempted mid-run resumes
   bitwise-identical to an uninterrupted run, and the ledger's registry
   identity makes resuming against a different registry a loud error.
"""

from __future__ import annotations

import numpy as np
import pytest

import fedml_tpu as fedml
from fedml_tpu import data as data_mod
from fedml_tpu import models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.scale import (
    ClientRegistry,
    ShardPrefetcher,
    cohort_key,
    make_shardings,
    match_partition_rules,
    named_tree_paths,
    parse_partition_rules,
)
from fedml_tpu.simulation.mesh_api import MeshFedAvgAPI
from fedml_tpu.simulation.sp_api import FedAvgAPI


def _make_api(backend="sp", cls=None, **kw):
    base = dict(
        dataset="synthetic", model="lr", client_num_in_total=16,
        client_num_per_round=8, comm_round=4, epochs=1, batch_size=16,
        learning_rate=0.1, frequency_of_the_test=100, preempt_signals=False,
    )
    base.update(kw)
    args = fedml.init(Arguments(overrides=base), should_init_logs=False)
    ds, od = data_mod.load(args)
    cls = cls or (MeshFedAvgAPI if backend == "mesh" else FedAvgAPI)
    return cls(args, fedml.get_device(args), ds, model_mod.create(args, od))


def _leaves(api):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(api.global_params)]


def _close(api):
    if api.cohort_engine is not None:
        api.cohort_engine.close()


# ---------------------------------------------------------------------------
# 1. registry
# ---------------------------------------------------------------------------


class TestClientRegistry:
    def test_roundtrip_and_identity(self, tmp_path):
        reg = ClientRegistry.synthetic(1000, backing_shards=16, seed=7,
                                       weight_concentration=2.0)
        reg.note_participation(reg.sample(0, 32))
        path = str(tmp_path / "reg.npz")
        reg.save(path)
        back = ClientRegistry.load(path)
        assert back.num_clients == 1000
        np.testing.assert_array_equal(back.weights, reg.weights)
        np.testing.assert_array_equal(back.shard_ptrs, reg.shard_ptrs)
        np.testing.assert_array_equal(
            back.participation, reg.counters()["participation"]
        )
        assert back.identity() == reg.identity()
        # identity digests the sampling-relevant columns
        other = ClientRegistry.synthetic(1000, backing_shards=16, seed=8)
        assert other.identity() != reg.identity()

    def test_sampling_determinism_across_instances(self):
        a = ClientRegistry.synthetic(5000, backing_shards=10, seed=3)
        b = ClientRegistry.synthetic(5000, backing_shards=10, seed=3)
        for r in (0, 1, 17):
            np.testing.assert_array_equal(a.sample(r, 64), b.sample(r, 64))
        # different rounds → different cohorts; no replacement within one
        c0, c1 = a.sample(0, 64), a.sample(1, 64)
        assert not np.array_equal(c0, c1)
        assert len(np.unique(c0)) == 64
        assert c0.min() >= 0 and c0.max() < 5000

    def test_weighted_sampling_bias(self):
        w = np.ones(1000, np.float32)
        w[:10] = 200.0  # ten heavyweight clients
        reg = ClientRegistry(w, np.zeros(1000, np.int32), seed=0)
        hits = 0
        for r in range(20):
            hits += int((reg.sample(r, 50) < 10).sum())
        # heavyweights are ~2/3 of the total mass; uniform would give ~1%
        assert hits > 100

    def test_participation_and_staleness(self):
        reg = ClientRegistry.synthetic(100, backing_shards=4, seed=0)
        c0 = reg.sample(0, 10)
        reg.note_participation(c0)
        reg.note_participation(reg.sample(1, 10))
        counts = reg.counters()
        assert counts["participation"].sum() == 20
        assert (counts["staleness"][c0] <= 1).all()

    def test_shard_rows_map_and_bounds(self):
        reg = ClientRegistry.synthetic(128, backing_shards=8, seed=0)
        rows = reg.shard_rows(reg.sample(0, 16))
        assert rows.min() >= 0 and rows.max() < 8
        with pytest.raises(ValueError, match="cohort size"):
            reg.device_sampler(0)
        with pytest.raises(ValueError, match="cohort size"):
            reg.device_sampler(129)
        with pytest.raises(ValueError, match="strictly positive"):
            ClientRegistry(np.zeros(4), np.zeros(4, np.int32))
        with pytest.raises(ValueError, match="non-negative"):
            ClientRegistry(np.ones(4), np.array([0, 1, -3, 2], np.int32))
        with pytest.raises(ValueError, match="entries"):
            ClientRegistry(np.ones(4), np.zeros(4, np.int32),
                           participation=np.zeros(7, np.int32))

    def test_scaffold_refuses_aliased_registry(self):
        # 4000 virtual clients over 16 shards: every cohort holds duplicate
        # rows, so the per-client variate scatter would be order-dependent
        with pytest.raises(ValueError, match="SCAFFOLD"):
            _make_api(client_registry="4000", cohort_size=32,
                      federated_optimizer="SCAFFOLD")


# ---------------------------------------------------------------------------
# 2. prefetcher
# ---------------------------------------------------------------------------


class TestShardPrefetcher:
    def test_hit_serves_scheduled_buffer(self):
        pf = ShardPrefetcher(depth=2)
        try:
            pf.schedule("a", lambda: ("payload-a",))
            out = pf.take("a", lambda: ("fresh-a",))
            assert out == ("payload-a",)
        finally:
            pf.stop()

    def test_cold_take_never_blocks(self):
        pf = ShardPrefetcher(depth=1)
        try:
            assert pf.take("never-scheduled", lambda: 42) == 42
        finally:
            pf.stop()

    def test_never_serves_stale_shard(self):
        pf = ShardPrefetcher(depth=1)
        try:
            pf.schedule("round-1", lambda: "old-cohort")
            # the round asks for a DIFFERENT cohort: the buffered entry
            # must not be served under the wrong key
            assert pf.take("round-2", lambda: "right-cohort") == \
                "right-cohort"
        finally:
            pf.stop()

    def test_depth_zero_is_synchronous(self):
        pf = ShardPrefetcher(depth=0)
        assert not pf.schedule("a", lambda: 1)
        assert pf.take("a", lambda: 2) == 2
        stats = pf.stats()
        assert stats["overlap_fraction"] == 0.0  # fully exposed I/O
        pf.stop()

    def test_gather_error_degrades_to_sync(self):
        pf = ShardPrefetcher(depth=1)
        try:
            def boom():
                raise RuntimeError("disk on fire")

            pf.schedule("k", boom)
            assert pf.take("k", lambda: "recovered") == "recovered"
        finally:
            pf.stop()

    def test_eviction_bounds_memory(self):
        pf = ShardPrefetcher(depth=1)
        try:
            pf.schedule("k1", lambda: 1)
            pf.take("k1", lambda: 1)  # ensure k1 finished
            pf.schedule("k2", lambda: 2)
            pf.take("k2", lambda: 2)
            pf.schedule("k3", lambda: 3)  # evicts any parked k2 leftovers
            assert pf.take("k3", lambda: 3) == 3
        finally:
            pf.stop()

    def test_cohort_key_is_content_addressed(self):
        a = np.array([3, 1, 2])
        assert cohort_key(a) == cohort_key(np.array([3, 1, 2]))
        assert cohort_key(a) != cohort_key(np.array([1, 2, 3]))


# ---------------------------------------------------------------------------
# 3. partition rules
# ---------------------------------------------------------------------------


class TestPartitionRules:
    def _tree(self):
        return {
            "cohort": {"x": np.zeros((8, 4)), "y": np.zeros((8,))},
            "params": {"dense": {"w": np.zeros((4, 2)),
                                 "b": np.zeros((2,))}},
            "step": np.zeros(()),  # scalar: never partitioned
        }

    def test_named_paths(self):
        names = dict(named_tree_paths(self._tree()))
        assert "cohort/x" in names and "params/dense/w" in names

    def test_first_match_wins_and_scalar_exemption(self):
        from jax.sharding import PartitionSpec as P

        rules = [
            (r"^cohort/x$", P("clients", None)),
            (r"^cohort/", P("clients")),
            (r".*", P()),
        ]
        specs = match_partition_rules(rules, self._tree())
        assert specs["cohort"]["x"] == P("clients", None)
        assert specs["cohort"]["y"] == P("clients")
        assert specs["params"]["dense"]["w"] == P()
        assert specs["step"] == P()

    def test_no_match_fallback_and_strict_mode(self):
        from jax.sharding import PartitionSpec as P

        rules = [(r"^cohort/", P("clients"))]
        specs = match_partition_rules(rules, self._tree(),
                                      fallback=P())
        assert specs["params"]["dense"]["w"] == P()
        with pytest.raises(ValueError, match="no partition rule matches"):
            match_partition_rules(rules, self._tree(), fallback=None)

    def test_parse_syntax(self):
        from jax.sharding import PartitionSpec as P

        rules = parse_partition_rules(
            "cohort/.*=clients; embed=clients,tensor; big=data+fsdp; .*="
        )
        assert rules[0] == ("cohort/.*", P("clients"))
        assert rules[1] == ("embed", P("clients", "tensor"))
        assert rules[2] == ("big", P(("data", "fsdp")))
        assert rules[3] == (".*", P())
        assert parse_partition_rules("") == []
        with pytest.raises(ValueError, match="bad partition rule"):
            parse_partition_rules("no-equals-sign")
        with pytest.raises(ValueError, match="pattern"):
            parse_partition_rules("[unclosed=clients")

    def test_make_shardings_validates_axes(self):
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()), ("clients",))
        sh = make_shardings(mesh, {"a": P("clients"), "b": P()})
        assert sh["a"].spec == P("clients")
        with pytest.raises(ValueError, match="names axis"):
            make_shardings(mesh, {"a": P("tensor")})


# ---------------------------------------------------------------------------
# 4. engine integration: determinism, streaming parity, recompiles
# ---------------------------------------------------------------------------


class TestRegistryRounds:
    def test_cohorts_deterministic_and_in_range(self):
        api = _make_api(client_registry="4000", cohort_size=32)
        try:
            c0 = api._client_sampling(0)
            assert np.array_equal(c0, api._client_sampling(0))
            assert len(c0) == 32 and c0.max() < api.ds.client_num
            api2 = _make_api(client_registry="4000", cohort_size=32)
            try:
                assert np.array_equal(c0, api2._client_sampling(0))
            finally:
                _close(api2)
        finally:
            _close(api)

    def test_prefetch_run_bitwise_equals_synchronous_run(self):
        """The streamed path must never serve a stale/wrong shard: a run
        with the prefetcher on is BITWISE identical to one with it off."""
        sync = _make_api(client_registry="2000", cohort_size=24,
                         cohort_prefetch=0)
        pre = _make_api(client_registry="2000", cohort_size=24,
                        cohort_prefetch=1)
        try:
            for r in range(4):
                sync.run_round(r)
                pre.run_round(r)
            for a, b in zip(_leaves(sync), _leaves(pre)):
                assert np.array_equal(a, b)
            stats = pre.cohort_engine.stats()
            # rounds 1..3 were prefetched while 0..2 ran
            assert stats["gather_s"] > 0
        finally:
            _close(sync)
            _close(pre)

    def test_prefetch_overlap_is_measured(self):
        api = _make_api(client_registry="2000", cohort_size=16)
        try:
            for r in range(5):
                api.run_round(r)
            stats = api.cohort_engine.stats()
            assert stats["overlap_fraction"] > 0.0
        finally:
            _close(api)

    def test_zero_steady_state_recompiles(self):
        """Cohort resampling at registry scale must never recompile: the
        sampler takes the round as a traced scalar and the cohort shapes
        are static (pad-to-bucket)."""
        from fedml_tpu.core.mlops import telemetry

        telemetry.install_jax_listeners()
        api = _make_api(client_registry="3000", cohort_size=32)
        try:
            for r in range(2):  # warmup: compile wall lives here
                api.run_round(r)
            before = telemetry.registry().counter("jax.compiles")
            for r in range(2, 6):
                api.run_round(r)
            assert telemetry.registry().counter("jax.compiles") == before
        finally:
            _close(api)

    def test_superround_matches_per_round_registry_path(self):
        """The scan body samples with the registry's own jit'd sampler —
        the cohort trajectory (and so the params) must match per-round
        launches bitwise."""
        per = _make_api(client_registry="2000", cohort_size=8,
                        cohort_prefetch=0)
        scan = _make_api(client_registry="2000", cohort_size=8,
                         superround_k=4)
        try:
            for r in range(4):
                per.run_round(r)
            scan.run_rounds(0, 4)
            assert scan._superround_step is not None
            for a, b in zip(_leaves(per), _leaves(scan)):
                assert np.array_equal(a, b)
            # accounting was replayed host-side for the scanned rounds, and
            # the per-round path counts the SAME rounds — lookahead
            # sampling (the prefetcher peeks at round k) must not count
            part = scan.cohort_engine.registry.counters()["participation"]
            assert part.sum() == 4 * 8
            part_per = per.cohort_engine.registry.counters()["participation"]
            assert part_per.sum() == 4 * 8
        finally:
            _close(per)
            _close(scan)

    def test_cohort_size_requires_registry(self):
        with pytest.raises(ValueError, match="cohort_size requires"):
            Arguments(overrides=dict(cohort_size=8))


# ---------------------------------------------------------------------------
# 5. mesh: rule-driven sharding parity + registry on the mesh path
# ---------------------------------------------------------------------------


class LegacyFirstAxisMesh(MeshFedAvgAPI):
    """The pre-rules hard-coded placement, kept verbatim as the parity
    oracle: cohort arrays split on the first axis over ``clients``,
    everything else replicated."""

    def __init__(self, *a, **kw):
        from jax.sharding import NamedSharding, PartitionSpec as P

        super().__init__(*a, **kw)
        self._shard = NamedSharding(self.mesh, P("clients"))
        self._repl = NamedSharding(self.mesh, P())

    def _place_cohort(self, arrays):
        import jax

        cx, cy, cn = arrays
        return (
            jax.device_put(np.asarray(cx), self._shard),
            jax.device_put(np.asarray(cy), self._shard),
            jax.device_put(np.asarray(cn, np.int32), self._shard),
        )

    def _place(self, arr):
        import jax

        return jax.device_put(jax.device_get(arr), self._shard)

    def _prepare_round(self):
        import jax

        self.global_params = jax.device_put(self.global_params, self._repl)

    def _place_state(self, state):
        import jax

        return jax.tree.map(
            lambda x: jax.device_put(x, self._repl), state
        )


class TestMeshRuleParity:
    @pytest.mark.parametrize("kw", [
        dict(model="lr"),
        dict(model="mlp"),
        dict(model="lr", client_num_per_round=6),  # cohort padding
        dict(model="lr", federated_optimizer="SCAFFOLD"),
    ])
    def test_rule_driven_sharding_is_bitwise_equal_to_first_axis(self, kw):
        legacy = _make_api(backend="mesh", cls=LegacyFirstAxisMesh, **kw)
        ruled = _make_api(backend="mesh", **kw)
        for r in range(3):
            legacy.run_round(r)
            ruled.run_round(r)
        for a, b in zip(_leaves(legacy), _leaves(ruled)):
            assert a.dtype == b.dtype and np.array_equal(a, b), \
                "rule-driven mesh sharding diverged from first-axis"

    def test_registry_on_mesh_path(self):
        api = _make_api(backend="mesh", client_registry="2000",
                        cohort_size=24)
        try:
            for r in range(3):
                out = api.run_round(r)
            assert np.isfinite(float(np.asarray(out["train_loss"])))
        finally:
            _close(api)

    def test_custom_rules_still_converge(self):
        # an explicit rule string equivalent to the default: same results
        api = _make_api(
            backend="mesh",
            mesh_partition_rules="cohort/.*=clients",
            mesh_state_rules=".*=",
        )
        ref = _make_api(backend="mesh")
        for r in range(2):
            api.run_round(r)
            ref.run_round(r)
        for a, b in zip(_leaves(api), _leaves(ref)):
            assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# 6. crash-safety: resume with a registry-backed run
# ---------------------------------------------------------------------------


class TestRegistryResume:
    def test_preempt_resume_bitwise_parity(self, tmp_path):
        from fedml_tpu.core.runstate import (
            PreemptionError, RunLedger, preemption_guard,
        )

        reg_kw = dict(client_registry="2000", cohort_size=16,
                      comm_round=6, checkpoint_rounds=2)
        ref = _make_api(**dict(reg_kw, checkpoint_rounds=0))
        ref.train()
        ref_params = _leaves(ref)

        api1 = _make_api(**reg_kw,
                         checkpoint_dir=str(tmp_path / "ckpt"))
        orig = api1.run_round

        def hooked(r):
            out = orig(r)
            if r == 2:
                preemption_guard().request()
            return out

        api1.run_round = hooked
        preemption_guard().reset()
        with pytest.raises(PreemptionError):
            api1.train()
        preemption_guard().reset()

        led = RunLedger.for_checkpoint_dir(str(tmp_path / "ckpt"))
        assert led.last_round() == 2
        # the ledger's run_meta pins the registry identity
        meta = led.meta()
        assert meta["world"]["registry"]["num_clients"] == 2000
        assert meta["world"]["registry"]["cohort_size"] == 16

        api2 = _make_api(**reg_kw, checkpoint_dir=str(tmp_path / "ckpt"))
        api2.train()
        for a, b in zip(ref_params, _leaves(api2)):
            assert a.dtype == b.dtype and np.array_equal(a, b), \
                "registry-backed resume diverged from uninterrupted run"
        # committed cohorts are the deterministic registry cohorts
        rounds = {r["round"]: r["cohort"] for r in led.rounds()}
        assert sorted(rounds) == list(range(6))

    def test_resume_with_different_registry_is_loud(self, tmp_path):
        api1 = _make_api(client_registry="2000", cohort_size=16,
                         comm_round=2, checkpoint_rounds=1,
                         checkpoint_dir=str(tmp_path / "ckpt"))
        api1.train()
        api2 = _make_api(client_registry="4000", cohort_size=16,
                         comm_round=4, checkpoint_rounds=1,
                         checkpoint_dir=str(tmp_path / "ckpt"))
        with pytest.raises(RuntimeError, match="run_meta mismatch"):
            api2.train()
        _close(api2)


# ---------------------------------------------------------------------------
# 7. wire-format satellites: frame validation + array contract
# ---------------------------------------------------------------------------


class TestWireContracts:
    def test_truncated_tensor_frame_is_a_clean_error(self):
        from fedml_tpu.core.distributed.tensor_transport import (
            decode_frames, encode_frames,
        )

        body = encode_frames([np.arange(32, dtype=np.float32)])
        with pytest.raises(ValueError, match="truncated tensor frame"):
            decode_frames(body[:-8])

    def test_corrupt_frame_header_is_a_clean_error(self):
        import json

        from fedml_tpu.core.distributed.tensor_transport import (
            RAW_MAGIC, decode_frames,
        )

        header = json.dumps(
            [{"dtype": "not-a-dtype", "shape": [4], "off": 0}]
        ).encode()
        body = (RAW_MAGIC + len(header).to_bytes(4, "big") + header
                + b"\x00" * 16)
        with pytest.raises(ValueError, match="corrupt tensor frame header"):
            decode_frames(body)
        header2 = json.dumps(
            [{"dtype": "<f4", "shape": [4], "off": -3}]
        ).encode()
        body2 = (RAW_MAGIC + len(header2).to_bytes(4, "big") + header2
                 + b"\x00" * 16)
        with pytest.raises(ValueError, match="corrupt tensor frame header"):
            decode_frames(body2)
        # adversarial shape that would wrap int64 under np.prod: must hit
        # the clean bounds error, not a raw numpy failure mid-decode
        header3 = json.dumps(
            [{"dtype": "<f4", "shape": [2 ** 40, 2 ** 40], "off": 0}]
        ).encode()
        body3 = (RAW_MAGIC + len(header3).to_bytes(4, "big") + header3
                 + b"\x00" * 16)
        with pytest.raises(ValueError, match="truncated tensor frame"):
            decode_frames(body3)
        # bit-flipped header bytes: a clean error, not a raw JSON failure
        good = json.dumps([{"dtype": "<f4", "shape": [2], "off": 0}]).encode()
        flipped = bytes([good[0] ^ 0xFF]) + good[1:]
        body4 = (RAW_MAGIC + len(flipped).to_bytes(4, "big") + flipped
                 + b"\x00" * 8)
        with pytest.raises(ValueError, match="corrupt tensor frame header"):
            decode_frames(body4)

    def test_registry_mode_skips_resident_dataset_copy(self):
        # streaming rounds must not park a dead HBM copy of the dataset;
        # superround is the exception (its scan gathers on device)
        api = _make_api(client_registry="2000", cohort_size=16)
        try:
            assert not api.hbm_resident
        finally:
            _close(api)
        scan = _make_api(client_registry="2000", cohort_size=8,
                         superround_k=2)
        try:
            assert scan.hbm_resident  # the scan body needs _dev_x et al.
        finally:
            _close(scan)

    def test_get_arrays_copy_contract(self):
        from fedml_tpu.core.distributed.message import Message

        msg = Message("t", 1, 2)
        msg.set_arrays([np.arange(8, dtype=np.float32)])
        msg.wire_format = "raw"
        back = Message.deserialize(msg.serialize())
        view = back.get_arrays()[0]
        # zero-copy views over the wire buffer are READ-ONLY
        assert not view.flags.writeable
        with pytest.raises(ValueError):
            view[0] = 99.0
        # the documented opt-in: fresh writable arrays, independent buffer
        writable = back.get_arrays(copy=True)[0]
        assert writable.flags.writeable
        writable[0] = 99.0
        np.testing.assert_array_equal(back.get_arrays()[0],
                                      np.arange(8, dtype=np.float32))
