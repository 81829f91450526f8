"""End-to-end simulation tests — the framework's version of the reference's
smoke tests (``python/tests/smoke_test/simulation_sp/main.py``; SURVEY.md §4
"tiny-config real training"), plus convergence assertions the reference never
had. Runs on the 8-device virtual CPU mesh from conftest.py.
"""

import jax
import numpy as np
import pytest

import fedml_tpu as fedml
from fedml_tpu import data as data_mod
from fedml_tpu import models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.runner import FedMLRunner


def run_sim(**kw):
    base = dict(
        dataset="synthetic", model="lr", client_num_in_total=16,
        client_num_per_round=8, comm_round=6, epochs=1, batch_size=16,
        learning_rate=0.1, frequency_of_the_test=10, backend="sp",
    )
    base.update(kw)
    args = fedml.init(Arguments(overrides=base), should_init_logs=False)
    dataset, output_dim = data_mod.load(args)
    model = model_mod.create(args, output_dim)
    runner = FedMLRunner(args, fedml.get_device(args), dataset, model)
    return runner.run()


class TestSPFedAvg:
    def test_fedavg_converges(self):
        res = run_sim(comm_round=10, epochs=2)
        assert res["test_acc"] > 0.9

    def test_fedavg_deterministic(self):
        a = run_sim(comm_round=3)
        b = run_sim(comm_round=3)
        assert a["test_acc"] == pytest.approx(b["test_acc"])
        assert a["test_loss"] == pytest.approx(b["test_loss"])

    @pytest.mark.parametrize("opt", ["FedProx", "FedNova", "SCAFFOLD", "FedSGD"])
    def test_optimizer_family_learns(self, opt):
        res = run_sim(federated_optimizer=opt)
        assert res["test_acc"] > 0.5  # well above 10-class chance

    def test_fedopt_adam(self):
        res = run_sim(federated_optimizer="FedOpt", server_optimizer="adam",
                      server_lr=0.03)
        assert res["test_acc"] > 0.5

    @pytest.mark.slow
    def test_cnn_on_mnist(self):
        res = run_sim(dataset="mnist", model="cnn", client_num_in_total=8,
                      client_num_per_round=8, comm_round=6, epochs=2,
                      batch_size=8, learning_rate=0.05)
        assert res["test_acc"] > 0.8

    @pytest.mark.slow
    def test_rnn_nwp_learns(self):
        res = run_sim(dataset="shakespeare", model="rnn",
                      client_num_in_total=4, client_num_per_round=4,
                      comm_round=6, epochs=3, batch_size=8,
                      client_optimizer="adam", learning_rate=0.01)
        # synthetic Markov stream: bigram-optimal accuracy is ~25%
        assert res["test_acc"] > 0.15


class TestMeshSimulator:
    def test_mesh_matches_sp_closely(self):
        """Mesh and SP run the same math; accuracy must agree to a few %."""
        sp = run_sim(backend="sp", comm_round=5)
        mesh = run_sim(backend="mesh", comm_round=5)
        assert mesh["test_acc"] > 0.5
        assert abs(sp["test_acc"] - mesh["test_acc"]) < 0.15

    def test_mesh_uses_all_devices(self):
        assert len(jax.devices()) == 8  # conftest forced 8 virtual devices
        res = run_sim(backend="mesh", client_num_per_round=8)
        assert res["test_acc"] > 0.5

    def test_mesh_with_cohort_padding(self):
        # cohort size 6 over 8 shards → 2 padded slots with zero weight
        res = run_sim(backend="mesh", client_num_per_round=6, comm_round=4)
        assert res["test_acc"] > 0.4


@pytest.mark.parametrize("extra", [
    dict(backend="sp"),
    dict(backend="mesh", mesh_shape="clients:8"),
], ids=["sp", "mesh"])
def test_runner_rounds_are_fused(tmp_path, extra):
    """Through FedMLRunner with telemetry on: every round is the jitted
    round, nothing compiles after round 0, the loss falls, and under mesh
    each device holds one client of the cohort."""
    from fedml_tpu.core import mlops

    args = fedml.init(Arguments(overrides=dict(
        dataset="synthetic", model="lr", client_num_in_total=16,
        client_num_per_round=8, comm_round=3, epochs=1, batch_size=16,
        learning_rate=0.1, frequency_of_the_test=1000,
        enable_tracking=True, tracking_dir=str(tmp_path),
        run_id=f"fused-{extra['backend']}", **extra,
    )), should_init_logs=False)
    ds, od = data_mod.load(args)
    runner = FedMLRunner(args, fedml.get_device(args), ds,
                         model_mod.create(args, od))
    runner.run()
    records = [e for e in mlops.read_events()
               if e.get("kind") == "round_record"]
    mlops.close()
    api = runner.runner.fl_trainer
    assert api._round_step is not None
    assert len(records) == 3 and all(r["fused"] for r in records)
    assert records[0]["compiles"] > 0
    assert sum(r["compiles"] for r in records[1:]) == 0
    assert records[-1]["train_loss"] < records[0]["train_loss"]
    if extra["backend"] == "mesh":
        cohort, _ = api._pad_cohort(np.arange(8))
        cohort_x, _, _ = api._gather_cohort(cohort)
        assert {d.id for d in cohort_x.sharding.device_set} == set(range(8))
        assert cohort_x.addressable_shards[0].data.shape[0] == 1


class TestTrustHooks:
    """The attack → defend → aggregate → DP pipeline must behave identically
    on the single-device (sp) and client-sharded (mesh) engines — the mesh
    path is exactly where the trust layer matters most."""

    @pytest.mark.parametrize("backend", ["sp", "mesh"])
    def test_defense_neutralizes_byzantine(self, backend):
        atk = dict(enable_attack=True, attack_type="byzantine_random",
                   byzantine_client_frac=0.3, byzantine_scale=30.0,
                   comm_round=8, backend=backend)
        poisoned = run_sim(**atk)
        defended = run_sim(**atk, enable_defense=True,
                           defense_type="multikrum", byzantine_client_num=3)
        assert poisoned["test_acc"] < 0.3  # attack destroys training
        assert defended["test_acc"] > 0.5  # multikrum excludes the outliers

    @pytest.mark.parametrize("backend", ["sp", "mesh"])
    def test_ldp_still_learns(self, backend):
        res = run_sim(enable_dp=True, dp_type="ldp", mechanism_type="gaussian",
                      epsilon=50.0, comm_round=8, backend=backend)
        assert res["test_acc"] > 0.4

    def test_cdp_noise_applied(self):
        clean = run_sim(comm_round=2)
        noised = run_sim(comm_round=2, enable_dp=True, dp_type="cdp",
                         mechanism_type="gaussian", epsilon=0.5)
        assert clean["test_acc"] != pytest.approx(noised["test_acc"])

    def test_mesh_defense_with_cohort_padding(self):
        """6 real clients pad to 8 shards; multikrum must only ever see the
        6 real rows (padding rows would otherwise skew its neighbour sums)."""
        res = run_sim(backend="mesh", client_num_per_round=6, comm_round=6,
                      enable_defense=True, defense_type="multikrum",
                      byzantine_client_num=1)
        assert res["test_acc"] > 0.5

    @pytest.mark.parametrize("opt", ["FedOpt", "FedSGD", "SCAFFOLD"])
    def test_mesh_optimizer_family(self, opt):
        """Server-optimizer + control-variate paths on the sharded engine."""
        kw = dict(backend="mesh", federated_optimizer=opt, comm_round=6)
        if opt == "FedOpt":
            kw.update(server_optimizer="adam", server_lr=0.03)
        res = run_sim(**kw)
        assert res["test_acc"] > 0.5

    def test_fedsgd_reports_loss(self):
        """Weak-item fix: FedSGD used to report train_loss = nan."""
        import fedml_tpu as fedml
        from fedml_tpu.arguments import Arguments
        from fedml_tpu import data as data_mod, models as model_mod
        from fedml_tpu.simulation.sp_api import FedAvgAPI

        args = fedml.init(Arguments(overrides=dict(
            dataset="synthetic", model="lr", client_num_in_total=8,
            client_num_per_round=4, comm_round=2, epochs=1, batch_size=16,
            learning_rate=0.1, federated_optimizer="FedSGD",
        )), should_init_logs=False)
        ds, out_dim = data_mod.load(args)
        api = FedAvgAPI(args, fedml.get_device(args), ds,
                        model_mod.create(args, out_dim))
        m = api.run_round(0)
        assert np.isfinite(m["train_loss"])


class TestCustomSeams:
    def test_custom_server_aggregator(self):
        from fedml_tpu.ml.aggregator import DefaultServerAggregator

        calls = {"before": 0, "after": 0}

        class MyAgg(DefaultServerAggregator):
            def on_before_aggregation(self, raw):
                calls["before"] += 1
                return raw

            def on_after_aggregation(self, agg):
                calls["after"] += 1
                return agg

        args = fedml.init(Arguments(overrides=dict(
            dataset="synthetic", model="lr", client_num_in_total=8,
            client_num_per_round=4, comm_round=2, epochs=2, batch_size=16,
            learning_rate=0.2,
        )), should_init_logs=False)
        ds, od = data_mod.load(args)
        bundle = model_mod.create(args, od)
        agg = MyAgg(bundle, args)
        runner = FedMLRunner(args, fedml.get_device(args), ds, bundle,
                             server_aggregator=agg)
        res = runner.run()
        assert calls["before"] == 2 and calls["after"] == 2
        assert res["test_acc"] > 0.3

    def test_custom_aggregator_with_defense_raises(self):
        """Defense replaces the aggregation rule — combining it with a user
        ServerAggregator must error, not silently drop the override."""
        from fedml_tpu.ml.aggregator import DefaultServerAggregator
        from fedml_tpu.simulation.sp_api import FedAvgAPI

        args = fedml.init(Arguments(overrides=dict(
            dataset="synthetic", model="lr", client_num_in_total=8,
            client_num_per_round=4, comm_round=1, epochs=1, batch_size=16,
            learning_rate=0.1, enable_defense=True, defense_type="krum",
            byzantine_client_num=1,
        )), should_init_logs=False)
        ds, od = data_mod.load(args)
        bundle = model_mod.create(args, od)
        with pytest.raises(ValueError, match="mutually exclusive"):
            FedAvgAPI(args, fedml.get_device(args), ds, bundle,
                      server_aggregator=DefaultServerAggregator(bundle, args))

    def test_custom_aggregator_composes_with_model_attack(self):
        """A model attack transforms client rows; the user's aggregation
        rule must still run on the attacked rows (was: silently bypassed)."""
        from fedml_tpu.ml.aggregator import DefaultServerAggregator

        calls = {"agg": 0}

        class MyAgg(DefaultServerAggregator):
            def aggregate(self, raw):
                calls["agg"] += 1
                return super().aggregate(raw)

        args = fedml.init(Arguments(overrides=dict(
            dataset="synthetic", model="lr", client_num_in_total=8,
            client_num_per_round=4, comm_round=2, epochs=1, batch_size=16,
            learning_rate=0.1, enable_attack=True,
            attack_type="byzantine_zero", byzantine_client_frac=0.25,
        )), should_init_logs=False)
        ds, od = data_mod.load(args)
        bundle = model_mod.create(args, od)
        runner = FedMLRunner(args, fedml.get_device(args), ds, bundle,
                             server_aggregator=MyAgg(bundle, args))
        runner.run()
        assert calls["agg"] == 2


class TestRoundCheckpointResume:
    """FL-round checkpoint/resume (r5; the reference restarts killed runs
    from round 0 — SURVEY §5). A run killed mid-federation must resume at
    the next round with the saved global and finish IDENTICALLY to an
    uninterrupted run (same cohorts, same rngs — both are round-keyed)."""

    def _api(self, tmp_path, rounds, **kw):
        from fedml_tpu.simulation.sp_api import FedAvgAPI

        args = fedml.init(Arguments(overrides=dict(
            dataset="synthetic", model="lr", client_num_in_total=16,
            client_num_per_round=8, comm_round=rounds, epochs=1,
            batch_size=16, learning_rate=0.1, frequency_of_the_test=100,
            checkpoint_dir=str(tmp_path / "ckpt"), **kw,
        )), should_init_logs=False)
        ds, od = data_mod.load(args)
        return FedAvgAPI(args, fedml.get_device(args), ds,
                         model_mod.create(args, od)), ds

    def test_sp_resume_matches_uninterrupted(self, tmp_path):
        import numpy as np

        # uninterrupted 6-round reference run (no checkpointing)
        from fedml_tpu.simulation.sp_api import FedAvgAPI

        args = fedml.init(Arguments(overrides=dict(
            dataset="synthetic", model="lr", client_num_in_total=16,
            client_num_per_round=8, comm_round=6, epochs=1, batch_size=16,
            learning_rate=0.1, frequency_of_the_test=100,
        )), should_init_logs=False)
        ds, od = data_mod.load(args)
        ref = FedAvgAPI(args, fedml.get_device(args), ds,
                        model_mod.create(args, od))
        ref.train()

        # "crash" after 3 rounds, then a FRESH api resumes and finishes
        api1, _ = self._api(tmp_path, rounds=3)
        api1.train()
        api2, _ = self._api(tmp_path, rounds=6)
        api2.train()
        assert [e["round"] for e in api2.history] == [3, 4, 5]  # resumed

        for a, b in zip(
            __import__("jax").tree.leaves(ref.global_params),
            __import__("jax").tree.leaves(api2.global_params),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

        # re-invoking a COMPLETED federation trains nothing and still
        # returns metrics of the restored model (not an empty dict)
        api3, _ = self._api(tmp_path, rounds=6)
        res3 = api3.train()
        assert api3.history == [] and "test_acc" in res3

    def test_cross_silo_server_resume(self, tmp_path):
        """A restarted cross-silo server resumes at the saved round: the
        second world runs only the remaining rounds and reaches FINISH."""
        import threading
        import time as _time

        from fedml_tpu.cross_silo import (
            FedMLCrossSiloClient, FedMLCrossSiloServer,
        )

        def world(run_id, rounds):
            def mk(role, rank=0):
                return fedml.init(Arguments(overrides=dict(
                    training_type="cross_silo", dataset="synthetic",
                    model="lr", client_num_in_total=2, client_num_per_round=2,
                    comm_round=rounds, epochs=1, batch_size=8,
                    learning_rate=0.2, backend="LOOPBACK", run_id=run_id,
                    role=role, rank=rank,
                    checkpoint_dir=str(tmp_path / "silo_ckpt"),
                )), should_init_logs=False)

            args_s = mk("server")
            ds, od = data_mod.load(args_s)
            bundle = model_mod.create(args_s, od)
            server = FedMLCrossSiloServer(args_s, None, ds, bundle)
            clients = [
                FedMLCrossSiloClient(mk("client", r), None, ds, bundle)
                for r in (1, 2)
            ]
            threads = [threading.Thread(target=c.run, daemon=True)
                       for c in clients]
            for t in threads:
                t.start()
            _time.sleep(0.05)
            res = server.run()
            for t in threads:
                t.join(timeout=60)
            return res, server

        _, s1 = world("ckpt-w1", rounds=2)
        assert s1.manager.round_idx == 2
        # restart with a LARGER budget: resumes at round 2, runs 2..3
        res2, s2 = world("ckpt-w2", rounds=4)
        assert s2.manager.round_idx == 4
        assert res2 is not None and "test_acc" in res2
        # restarting the COMPLETED federation must not train a round past
        # the budget: clients get FINISH immediately, round index unmoved
        res3, s3 = world("ckpt-w3", rounds=4)
        assert s3.manager.round_idx == 4
        assert res3 is not None and "test_acc" in res3
