"""The yardstick itself: shape functions against hand counts, and the plain
references against the code they will judge, at tiny sizes on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.flops import resnet, transformer
from fedml_tpu.parallel.sharding import unbox
from benchmark.reference import fedavg_round, mistral, resnet56_gn

MISTRAL = dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
               intermediate_size=14336, vocab_size=32000)


def test_resnet56_flops_by_hand():
    """2 * pixels * k*k * cin * cout per convolution, counted layer by layer."""
    stem = 2 * 32 * 32 * 9 * 3 * 16
    stage1 = 18 * (2 * 32 * 32 * 9 * 16 * 16)
    stage2 = (2 * 16 * 16 * 9 * 16 * 32) + 17 * (2 * 16 * 16 * 9 * 32 * 32) \
        + (2 * 16 * 16 * 1 * 16 * 32)
    stage3 = (2 * 8 * 8 * 9 * 32 * 64) + 17 * (2 * 8 * 8 * 9 * 64 * 64) \
        + (2 * 8 * 8 * 1 * 32 * 64)
    dense = 2 * 64 * 10
    forward = stem + stage1 + stage2 + stage3 + dense
    assert forward == 251_495_680
    args = dict(stage_sizes=[9, 9, 9], stage_filters=[16, 32, 64],
                image_hw=32, in_channels=3, num_classes=10)
    assert resnet.resnet_cifar_forward_flops(**args) == forward
    # backward: twice the forward, less the stem's input gradient
    assert resnet.resnet_cifar_train_flops(**args) == 3 * forward - stem == 753_602_304


@pytest.mark.parametrize("layers, want", [(2, 3_605_053_440), (3, 5_014_364_160)])
def test_mistral_block_flops_by_hand(layers, want):
    """Per token, forward: q, o 2*4096*4096 each; k, v 2*4096*1024 each;
    gate, up, down 2*4096*14336 each; causal scores and values
    2*2*4096*(4097/2); head 2*4096*32000. Training is three times that; the
    embedding gather is not in it."""
    proj = 2 * (2 * 4096 * 4096) + 2 * (2 * 4096 * 1024)
    ffn = 3 * (2 * 4096 * 14336)
    attn = 2 * 2 * 4096 * 4097 / 2
    head = 2 * 4096 * 32000
    assert 3 * (layers * (proj + ffn + attn) + head) == want
    config = dict(MISTRAL, num_hidden_layers=layers)
    assert transformer.train_flops_per_token(config, 4096) == want
    # what the configuration files say about the head's share of the matmuls
    share = head / (layers * (proj + ffn) + head)
    assert round(100 * share) == {2: 23, 3: 17}[layers]


def _tiny_lm(dtype):
    """The program's Transformer at a tiny size, and the same sizes under the
    names the reference reads."""
    from fedml_tpu.parallel.transformer import Transformer, TransformerConfig

    cfg = TransformerConfig(vocab_size=96, d_model=64, n_layers=2, n_heads=4,
                            n_kv_heads=2, d_ff=160, max_seq_len=128,
                            remat=False, dtype=dtype, attn_impl="xla")
    config = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                  intermediate_size=160, vocab_size=96, num_hidden_layers=2,
                  rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                  sliding_window=128)
    return Transformer(cfg), config


# float32 on both sides: only the order of float32 sums differs. bfloat16 (the
# program's default): inputs of every matmul are rounded to 8 bits of
# mantissa, 4e-3 each; two blocks leave about that on the logits. A dropped
# rotation, a wrong head grouping or a missing causal mask moves them by O(1)
# (checked below), and rounding to fewer bits than bfloat16 would fail 2e-2.
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_mistral_reference_agrees_with_the_programs_transformer(dtype, tol):
    pretrain = harness.load_module(harness.ROOT, "jobs", "pretrain")
    model, config = _tiny_lm(dtype)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 96)
    params = unbox(model.init(jax.random.PRNGKey(0), tokens)["params"])
    got = np.asarray(model.apply({"params": params}, tokens))
    plain = pretrain.reference_params(params, config)
    for row in range(2):
        loss_sum, tail = mistral.loss_sum_and_tail_logits(
            plain, tokens[row], config, tail=128)
        err = np.linalg.norm(got[row] - np.asarray(tail)) / np.linalg.norm(tail)
        assert err < tol, err
        logp = jax.nn.log_softmax(got[row, :-1])
        want = -np.take_along_axis(np.asarray(logp), np.asarray(tokens[row, 1:, None]), 1).sum()
        assert abs(float(loss_sum) - want) / want < tol


def test_mistral_reference_is_sensitive_to_what_it_checks():
    """The tolerance means something: each of these mistakes in the reference
    moves the logits by far more than it allows."""
    pretrain = harness.load_module(harness.ROOT, "jobs", "pretrain")
    model, config = _tiny_lm(jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (128,), 0, 96)
    params = unbox(model.init(jax.random.PRNGKey(0), tokens[None])["params"])
    plain = pretrain.reference_params(params, config)
    # at width 64 the initial scores are ~0.03 and attention is a plain mean,
    # whatever the rotation; at width 4096 they are ~1.6. Widen q and k so
    # that this tiny model attends as sharply as the real one does.
    plain["layers"] = [dict(l, wq=8 * l["wq"], wk=8 * l["wk"])
                       for l in plain["layers"]]
    _, want = mistral.loss_sum_and_tail_logits(plain, tokens, config, 64)

    def err(p, c):
        _, got = mistral.loss_sum_and_tail_logits(p, tokens, c, 64)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    assert err(plain, dict(config, rope_theta=500000.0)) > 0.05
    swapped = dict(plain, layers=[dict(l, wk=l["wv"], wv=l["wk"])
                                  for l in plain["layers"]])
    assert err(swapped, config) > 0.05
    with pytest.raises(ValueError, match="sliding_window"):
        mistral.hidden_states(plain, tokens, dict(config, sliding_window=64))


def test_resnet56_reference_agrees_with_the_programs_model():
    """Same parameters, same images, float32 on XLA:CPU on both sides: the
    only difference is the order of float32 sums (GroupNorm's variance as
    E[(x-m)^2] here, E[x^2]-m^2 in flax), so 1e-4 relative."""
    from fedml_tpu.models.vision import resnet56

    module = resnet56(10)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 32, 32, 3))
    variables = module.init(jax.random.PRNGKey(0), x, train=False)
    # move scale and bias off their initial 1 and 0 so that they are checked
    variables = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(p.size), p.shape),
        variables)
    got = module.apply(variables, x, train=False)
    want = resnet56_gn.forward(variables, x, stage_sizes=[9, 9, 9])
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-4


@pytest.mark.parametrize("partition", ["homo", "hetero"])
def test_plain_fedavg_round_agrees_with_the_round_engine(partition):
    """One fused round of the program against the per-client, per-batch replay
    on the same seeded data (ragged clients under ``hetero``: padding masks
    and weights by true counts are what it checks). Float32 both sides on the
    CPU: 1e-5 relative on the aggregated update."""
    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.sp_api import FedAvgAPI

    logreg = harness.load_module(
        harness.ROOT + "/tests/benchmark/fixture_root", "reference", "logreg")
    args = fedml.init(Arguments(overrides=dict(
        training_type="simulation", backend="sp", dataset="synthetic",
        model="lr", client_num_in_total=12, client_num_per_round=5,
        comm_round=1, epochs=2, batch_size=8, learning_rate=0.05,
        partition_method=partition, partition_alpha=0.3, random_seed=7,
        frequency_of_the_test=1000)), should_init_logs=False)
    ds, classes = data_mod.load(args)
    api = FedAvgAPI(args, None, ds, model_mod.create(args, classes))
    before = jax.tree.map(jnp.copy, api.global_params)
    api.run_round(0)
    cohort = fedavg_round.sample_cohort(0, ds.client_num, 5)
    want, _ = fedavg_round.replay_round(
        logreg.forward, before, ds.train_x[cohort], ds.train_y[cohort],
        ds.train_counts[cohort], seed=7, round_idx=0, batch_size=8, epochs=2,
        lr=0.05)
    fedavg = harness.load_module(harness.ROOT, "jobs", "fedavg")
    delta = lambda new: jax.tree.map(lambda a, b: a - b, new, before)
    assert fedavg.rel_l2(delta(api.global_params), delta(want)) < 1e-5
    if partition == "hetero":
        assert len(set(ds.train_counts[cohort].tolist())) > 1  # ragged indeed
