"""Round engine tests (simulation/round_engine.py, simulation/sp_api.py).

There is one definition of a FedAvg-family round, ``build_round_core``; how
it is executed is decided from the configuration. Pinned here:

1. **Jitted == eager**: the jitted, donated round produces the same global
   params as the same ``core`` called un-jitted on a second API (atol 1e-5,
   and in practice bitwise on most paths) for every FedAvg-family optimizer
   and the DP/attack/defense trust paths, on both the sp and mesh backends:
   tracing bakes the attacker's seeded masks as the eager run computes them,
   donation and the padding slices change nothing, every optimizer's state
   is plumbed through. (The independent reference for the arithmetic is
   ``tests/benchmark/test_benchmark_shapes_references.py``.)
2. **The program is the parent's**: the lowered round of PR 30's parent
   commit, by hash.
3. **Host rules**: a custom ``ServerAggregator``, FL-WBC, ``TurboAggregateAPI``
   and ``HierarchicalFLAPI`` run without a jitted round, everything else
   with one.
4. **Donation safety**: the round state really is donated (use-after-donate
   raises), and ``CheckpointManager.save`` copies every leaf to host BEFORE
   the next round's dispatch can invalidate the buffers — so checkpoint /
   resume matches an uninterrupted run exactly.
5. **Recompilation regression guard**: steady state is ONE compile of the
   round program per (backend, optimizer) config — 5 rounds, cache
   size 1 (lowering-cache inspection via ``jit._cache_size()``).
6. **Cohort chunks**: the rule that says how many clients share one batched
   program, as a pure function and through the three engines; chunked ==
   ``vmap`` (a chunk that does not divide the cohort, SCAFFOLD's extra axes,
   whole rounds); a convolutional model's lowered round holds no convolution
   grouped over more than ``M_CONV`` clients.
7. **Superround**: K rounds per launch under ``lax.scan`` with on-device
   sampling — under full participation (sampling degenerates to ``arange``
   on both paths) it matches single eager rounds exactly; eval/checkpoint
   schedules are preserved by the chunker; at most two programs compile.
"""

from __future__ import annotations

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import orbax.checkpoint as ocp
import pytest

import fedml_tpu as fedml
from fedml_tpu import data as data_mod
from fedml_tpu import models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.ml.aggregator import DefaultServerAggregator
from fedml_tpu.ml.local_train import make_local_train_fn
from fedml_tpu.simulation import sp_api
from fedml_tpu.simulation.hierarchical_api import HierarchicalFLAPI
from fedml_tpu.simulation.mesh_api import MeshFedAvgAPI
from fedml_tpu.simulation.round_engine import build_round_core
from fedml_tpu.simulation.sp_api import (M_CONV, FedAvgAPI, _over_cohort,
                                         cohort_chunk_rule)
from fedml_tpu.simulation.turboaggregate_api import TurboAggregateAPI


def make_api(cls=FedAvgAPI, aggregator=None, **kw):
    base = dict(
        dataset="synthetic", model="lr", client_num_in_total=16,
        client_num_per_round=8, comm_round=3, epochs=1, batch_size=16,
        learning_rate=0.1, frequency_of_the_test=100,
    )
    base.update(kw)
    args = fedml.init(Arguments(overrides=base), should_init_logs=False)
    ds, od = data_mod.load(args)
    bundle = model_mod.create(args, od)
    return cls(args, fedml.get_device(args), ds, bundle,
               server_aggregator=aggregator and aggregator(bundle, args))


class EagerRounds:
    """A second API whose rounds are ``build_round_core``'s function called
    un-jitted: no program, no donation, every op dispatched by itself."""

    def __init__(self, **kw):
        self.api = api = make_api(**kw)
        per = api._cohort_size()
        cohort0, _ = api._pad_cohort(np.arange(per) % api.ds.client_num)
        self.core = build_round_core(api, n_cohort=len(cohort0), n_valid=per)

    def run_round(self, r):
        state, metrics = self.core(*self.api._round_inputs(r))
        self.api._set_round_state(state)
        return metrics


def assert_jitted_matches_eager(rounds=3, atol=1e-5, **kw):
    ref, api = EagerRounds(**kw), make_api(**kw)
    for r in range(rounds):
        mr, mj = ref.run_round(r), api.run_round(r)
        assert np.isclose(float(mj["train_loss"]), float(mr["train_loss"]),
                          atol=1e-5)
    assert api._round_step is not None
    assert max_param_diff(ref.api, api) < atol


def max_param_diff(a, b) -> float:
    la = jax.tree.leaves(a.global_params)
    lb = jax.tree.leaves(b.global_params)
    return max(
        float(np.abs(np.asarray(x) - np.asarray(y)).max())
        for x, y in zip(la, lb)
    )


class TestFusionParity:
    """The jitted, donated round vs the same core un-jitted, 3 rounds."""

    @pytest.mark.parametrize(
        "opt", ["FedAvg", "FedProx", "FedOpt", "FedNova", "SCAFFOLD", "FedSGD"]
    )
    def test_optimizer_parity(self, opt):
        kw = dict(federated_optimizer=opt)
        if opt == "FedOpt":
            kw.update(server_optimizer="adam", server_lr=0.03)
        assert make_api(**kw)._round_step is None  # built lazily
        assert_jitted_matches_eager(**kw)

    @pytest.mark.parametrize("dp_type", ["cdp", "ldp"])
    def test_dp_parity(self, dp_type):
        assert_jitted_matches_eager(
            enable_dp=True, dp_type=dp_type, mechanism_type="gaussian",
            epsilon=5.0)

    def test_attack_defense_parity(self):
        assert_jitted_matches_eager(
            enable_attack=True, attack_type="byzantine_random",
            byzantine_client_frac=0.3, byzantine_scale=30.0,
            enable_defense=True, defense_type="multikrum",
            byzantine_client_num=3)

    @pytest.mark.parametrize("kw", [
        dict(),
        dict(client_num_per_round=6),  # cohort padding + zero-weight mask
        dict(federated_optimizer="SCAFFOLD"),
    ])
    def test_mesh_parity(self, kw):
        assert_jitted_matches_eager(cls=MeshFedAvgAPI, **kw)


# sha256 of ``api._round_step.lower(..).as_text()`` at PR 30's parent commit
# (fd1aadb), JAX's numbering of its private helper functions taken out: PR 30
# moved the round's code and must not have moved the program. A change that
# means to alter the round program changes these on purpose.
PARENT_ROUND_PROGRAM = {
    "FedAvg": (dict(),
               "5875097cb1d9d1f7a969a42ae2508a390e4d6f8d688eaedee45551ddad790bfc"),
    "FedOpt": (dict(federated_optimizer="FedOpt", server_optimizer="adam",
                    server_lr=0.03),
               "ad6ce6419ad8b095833a8fb6c589e84df5477e4f91683852cac0449adf4f62b5"),
    "SCAFFOLD": (dict(federated_optimizer="SCAFFOLD"),
                 "5ad6b8f9c81e890fba88fe088300fac39433bf719253fe7933ef625e9da4563a"),
    # a convolutional model: on the CPU its cohort runs under lax.map
    "FedAvg-cnn": (dict(dataset="mnist", model="cnn", client_num_in_total=4,
                        client_num_per_round=2, batch_size=8),
                   "d83ff10c1e0139bec7828e4098bc8c561600dc717766c12813f0cc8a0639c143"),
}


@pytest.mark.parametrize("name", sorted(PARENT_ROUND_PROGRAM))
def test_round_program_lowers_as_at_the_parent_commit(name):
    kw, want = PARENT_ROUND_PROGRAM[name]
    api = make_api(**kw)
    api._setup_round()
    text = api._round_step.lower(*api._round_inputs(0)).as_text()
    text = re.sub(r"@(_?[A-Za-z_]+)_\d+", r"@\1", text)
    assert hashlib.sha256(text.encode()).hexdigest() == want


class TestHostRules:
    """Configurations whose aggregation rule is host Python run the round
    eagerly: ``_round_step`` stays None and the records say ``fused`` false."""

    def test_a_host_rule_runs_the_round_eagerly(self):
        api = make_api(aggregator=DefaultServerAggregator,
                       client_num_in_total=8, client_num_per_round=4)
        assert api._host_rule() is not None
        out = api.run_round(0)
        assert api._round_step is None and api._round is not None
        assert np.isfinite(float(out["train_loss"]))
        # and no configuration without one does
        plain = make_api()
        assert plain._host_rule() is None
        plain.run_round(0)
        assert plain._round is plain._round_step is not None

    def test_weighted_average_aggregator_matches_the_jitted_round(self):
        """A custom ServerAggregator that computes the weighted average
        stands where the in-program average stands: same parameters."""
        custom = make_api(aggregator=DefaultServerAggregator)
        jitted = make_api()
        for r in range(3):
            custom.run_round(r)
            jitted.run_round(r)
        assert custom._round_step is None and jitted._round_step is not None
        assert max_param_diff(custom, jitted) < 1e-5

    def test_wbc_runs_eagerly_with_concrete_client_ids(self, monkeypatch):
        api = make_api(enable_defense=True, defense_type="wbc")
        seen = []
        defend = api.defender.defend

        def spy(updates, weights, gvec, key, client_ids=None):
            seen.append([int(c) for c in client_ids])  # concrete: no tracer
            return defend(updates, weights, gvec, key, client_ids=client_ids)

        monkeypatch.setattr(api.defender, "defend", spy)
        for r in range(2):
            api.run_round(r)
        assert api._round_step is None
        assert seen == [[int(c) for c in api._client_sampling(r)]
                        for r in range(2)]
        # the history the defense keeps is keyed by those ids
        assert set(api.defender._wbc_old) == {c for ids in seen for c in ids}

    @pytest.mark.parametrize("cls, kw", [
        (TurboAggregateAPI, dict()),
        (HierarchicalFLAPI, dict(group_num=2, group_comm_round=2)),
    ])
    def test_subclass_rounds_never_build_a_jitted_round(self, cls, kw):
        """TurboAggregate's additive-share ``_aggregate`` is the round's host
        rule (a jitted round would degrade secure aggregation to a
        trusted-server average); HierarchicalFL replaces the whole round."""
        api = make_api(cls=cls, client_num_in_total=8,
                       client_num_per_round=4, **kw)
        before = jax.tree.leaves(api.global_params)[0].copy()
        out = api.run_round(0)
        assert api._round_step is None and api._superround_step is None
        assert np.isfinite(float(out["train_loss"]))
        assert not np.allclose(
            before, np.asarray(jax.tree.leaves(api.global_params)[0]))


# ---------------------------------------------------------------------------
# How many clients of a cohort share one batched program
# (``sp_api.cohort_chunk_rule`` and ``M_CONV``, which say why;
# ``sp_api._over_cohort``)
# ---------------------------------------------------------------------------

CNN = dict(dataset="mnist", model="cnn", client_num_in_total=8, batch_size=8)


@pytest.mark.parametrize("cohort", [1, 10, 50])
@pytest.mark.parametrize("sharded", [False, True])
@pytest.mark.parametrize("conv_model", [False, True])
def test_cohort_chunk_rule(conv_model, sharded, cohort):
    """A pure function of what the engine can observe, and not of the
    platform: it takes none."""
    got = cohort_chunk_rule(conv_model, sharded, cohort)
    if conv_model and not sharded:
        assert got == min(M_CONV, cohort) >= 1
    else:
        assert got == cohort  # one vmap over the whole cohort


@pytest.mark.parametrize("cls, kw, chunk", [
    (FedAvgAPI, dict(CNN, client_num_per_round=4), M_CONV),
    (FedAvgAPI, dict(client_num_per_round=8), 8),
    (FedAvgAPI, dict(client_num_per_round=8, federated_optimizer="SCAFFOLD"),
     8),
    # unless the cohort axis is sharded over a mesh
    (MeshFedAvgAPI, dict(CNN, client_num_per_round=8), 8),
])
def test_engines_follow_the_rule(cls, kw, chunk):
    assert make_api(cls=cls, **kw).cohort_chunk == chunk


def cohort_outputs(api, chunk, cohort):
    """``local_train`` over the first ``cohort`` clients, ``chunk`` at a
    time, from the API's own data, parameters and keys."""
    scaffold = api.scaffold
    fn = make_local_train_fn(api.bundle, api.args, api.ds.cap,
                             scaffold=scaffold)
    axes = (None, 0, 0, 0, 0) + ((None, 0) if scaffold else ())
    rows = np.arange(cohort)
    inputs = [api.global_params, jnp.asarray(api.ds.train_x[rows]),
              jnp.asarray(api.ds.train_y[rows]),
              jnp.asarray(api.ds.train_counts[rows].astype(np.int32)),
              jax.random.split(jax.random.PRNGKey(7), cohort)]
    if scaffold:
        # variates that differ by client and by leaf, so a wrong axis shows
        key = jax.random.PRNGKey(11)
        inputs.append(jax.tree.map(
            lambda p: 0.01 * jax.random.normal(key, p.shape), api.c_global))
        inputs.append(jax.tree.map(
            lambda p: 0.01 * jax.random.normal(key, (cohort,) + p.shape),
            api.c_global))
    return jax.jit(_over_cohort(fn, axes, chunk, cohort))(*inputs)


@pytest.mark.parametrize("name, kw, cohort, atol", [
    ("convex", dict(), 5, 1e-5),
    ("convex-scaffold", dict(federated_optimizer="SCAFFOLD"), 5, 1e-5),
    # float additions inside a convolution may be ordered differently; a
    # smaller cohort because XLA:CPU is slow on the vmapped side
    ("cnn", dict(CNN), 3, 1e-4),
    ("cnn-scaffold", dict(CNN, federated_optimizer="SCAFFOLD"), 3, 1e-4),
])
def test_chunked_cohort_equals_vmap(name, kw, cohort, atol):
    """Chunks of 2 that do not divide the cohort (2 + 2 + 1 of 5, 2 + 1 of
    3) and clients one at a time, against one vmap over the whole cohort."""
    api = make_api(**kw)
    want = cohort_outputs(api, cohort, cohort)
    for chunk in (1, 2):
        got = cohort_outputs(api, chunk, cohort)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=atol, rtol=0)


@pytest.mark.parametrize("opt", ["FedAvg", "SCAFFOLD", "FedSGD"])
def test_chunked_rounds_equal_vmap_rounds(opt, monkeypatch):
    """Three whole rounds, a cohort of 5 in chunks of 2 against one vmap:
    every consumer of ``cohort_fn`` in the round gets the same stacked
    outputs."""
    kw = dict(federated_optimizer=opt, client_num_per_round=5)
    whole = make_api(**kw)
    monkeypatch.setattr(sp_api, "cohort_chunk_rule", lambda *a: 2)
    chunked = make_api(**kw)
    assert (whole.cohort_chunk, chunked.cohort_chunk) == (5, 2)
    for r in range(3):
        lw, lc = whole.run_round(r), chunked.run_round(r)
        assert np.isclose(float(lw["train_loss"]), float(lc["train_loss"]),
                          atol=1e-5)
    assert chunked._round_step is not None
    assert max_param_diff(whole, chunked) < 1e-5


@pytest.mark.parametrize("sharded, widest", [(False, M_CONV), (True, 6)])
def test_resnet_round_holds_no_cohort_wide_convolution(sharded, widest,
                                                       monkeypatch):
    """The lowered round of ``resnet20`` at a cohort of 6: no convolution is
    grouped over more than ``M_CONV`` clients. (Told that the cohort axis is
    sharded the same engine lowers every convolution grouped over all 6: the
    count below sees what it is meant to see.)"""
    monkeypatch.setattr(FedAvgAPI, "cohort_sharded", sharded)
    api = make_api(dataset="cifar10", model="resnet20", client_num_in_total=8,
                   client_num_per_round=6, batch_size=8)
    assert api.cohort_chunk == widest
    api._setup_round()
    text = api._round_step.lower(*api._round_inputs(0)).as_text()
    groups = [int(g) for g in
              re.findall(r"feature_group_count = (\d+)", text)]
    assert len(groups) >= 3 * 20  # forward, input and filter gradients
    assert max(groups) == widest


class TestDonationSafety:
    def test_state_is_donated(self):
        api = make_api()
        api.run_round(0)  # builds the program; state now holds round-0 output
        old_leaf = jax.tree.leaves(api.global_params)[0]
        api.run_round(1)  # donates round-0 buffers
        with pytest.raises(RuntimeError):
            np.asarray(old_leaf)  # use-after-donate must raise, not read junk

    def test_checkpoint_copies_to_host_before_next_dispatch(self, tmp_path):
        from fedml_tpu.checkpoint import CheckpointManager

        api = make_api(federated_optimizer="SCAFFOLD")
        api.run_round(0)
        mgr = CheckpointManager(str(tmp_path / "ckpt"))
        received = {}
        orig_save = mgr._mgr.save

        def spy(step, args=None, **kw):
            received["state"] = args.item
            return orig_save(step, args=args, **kw)

        mgr._mgr.save = spy
        try:
            mgr.save(api._ckpt_state(), step=0)
            # every leaf orbax sees must already be a HOST array — a device
            # reference would be invalidated by the next round's donation
            assert all(
                isinstance(leaf, np.ndarray)
                for leaf in jax.tree.leaves(received["state"])
            )
            api.run_round(1)  # donates the checkpointed device buffers
            restored = mgr.restore_latest(api._ckpt_state())
            assert restored is not None  # checkpoint survives the donation
            for leaf in jax.tree.leaves(restored):
                np.asarray(leaf)  # every restored leaf is readable
        finally:
            mgr.close()

    @pytest.mark.parametrize("opt", ["FedAvg", "FedOpt", "SCAFFOLD"])
    def test_fused_resume_matches_uninterrupted(self, tmp_path, opt):
        kw = dict(federated_optimizer=opt)
        if opt == "FedOpt":
            kw.update(server_optimizer="adam", server_lr=0.03)
        ref = make_api(comm_round=6, **kw)
        ref.train()

        ck = dict(kw, checkpoint_dir=str(tmp_path / f"ck_{opt}"))
        api1 = make_api(comm_round=3, **ck)
        api1.train()  # "crash" after 3 rounds
        api2 = make_api(comm_round=6, **ck)
        api2.train()
        assert [e["round"] for e in api2.history] == [3, 4, 5]
        assert max_param_diff(ref, api2) < 1e-6


class TestRecompilationGuard:
    """Steady state = ONE compile of round_step per (backend, optimizer)."""

    @pytest.mark.parametrize("backend", [FedAvgAPI, MeshFedAvgAPI])
    @pytest.mark.parametrize("opt", ["FedAvg", "FedOpt"])
    def test_one_compile_across_five_rounds(self, backend, opt):
        kw = dict(federated_optimizer=opt, comm_round=5,
                  frequency_of_the_test=2)
        if opt == "FedOpt":
            kw.update(server_optimizer="adam", server_lr=0.03)
        api = make_api(cls=backend, **kw)
        api.train()
        assert len(api.history) == 5
        # lowering-cache inspection: one entry == one compile of round_step
        assert api._round_step._cache_size() == 1

    def test_losses_realized_as_floats(self):
        api = make_api(comm_round=4)
        api.train()
        for e in api.history:
            assert isinstance(e["train_loss"], float)
            assert np.isfinite(e["train_loss"])


class TestSuperround:
    FULL = dict(client_num_in_total=8, client_num_per_round=8,
                frequency_of_the_test=1000)

    def _mk(self, **kw):
        return make_api(**dict(self.FULL, **kw))

    def test_full_participation_matches_unfused_exactly(self):
        # full participation: both the host sampler and the on-device sampler
        # degenerate to arange, so the scan's trajectory must coincide bit
        # for bit with single rounds run eagerly
        ref = EagerRounds(**dict(self.FULL, comm_round=7))
        for r in range(7):
            ref.run_round(r)
        sup = self._mk(comm_round=7, superround_k=3)
        sup.train()
        assert [e["round"] for e in sup.history] == list(range(7))
        assert max_param_diff(ref.api, sup) < 1e-6
        # at most two programs: the K-scan and the single-round step
        assert sup._superround_step._cache_size() == 1
        assert sup._round_step._cache_size() <= 1

    def test_partial_participation_trains_and_is_deterministic(self):
        a = make_api(client_num_in_total=16, client_num_per_round=4,
                     comm_round=9, superround_k=4, frequency_of_the_test=1000)
        res_a = a.train()
        b = make_api(client_num_in_total=16, client_num_per_round=4,
                     comm_round=9, superround_k=4, frequency_of_the_test=1000)
        res_b = b.train()
        assert res_a["test_acc"] == pytest.approx(res_b["test_acc"])
        assert res_a["test_acc"] > 0.5
        assert [e["round"] for e in a.history] == list(range(9))

    def test_eval_schedule_preserved_under_chunking(self):
        # freq=2: an eval lands inside any 4-round chunk, so the chunker must
        # fall back to single rounds — and every eval round gets its metrics
        api = self._mk(comm_round=6, superround_k=4,
                       frequency_of_the_test=2)
        api.train()
        evaled = [e["round"] for e in api.history if "test_acc" in e]
        assert evaled == [0, 2, 4, 5]

    def test_superround_respects_checkpoint_schedule(self, tmp_path):
        api = self._mk(comm_round=8, superround_k=4,
                       checkpoint_dir=str(tmp_path / "ck"),
                       checkpoint_every_rounds=8)
        api.train()
        mgr = ocp.CheckpointManager(str(tmp_path / "ck"))
        try:
            assert mgr.latest_step() == 7
        finally:
            mgr.close()

    def test_run_rounds_helper_falls_back_without_superround(self):
        api = make_api(client_num_in_total=16, client_num_per_round=4,
                       comm_round=4)
        out = api.run_rounds(0, 3)  # no compiled K=3 scan: python loop
        assert len(out["train_loss"]) == 3
        assert api._superround_step is None
