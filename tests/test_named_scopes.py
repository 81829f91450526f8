"""Names on the device work that has no flax module (ISSUE 26).

Each jitted program, lowered small on the CPU, carries every name of its
vocabulary (``fedml_tpu/core/mlops/scopes.py``) in its debug locations, in
the configuration that builds it; a scope outside the vocabulary is refused
where it is opened. The trace's ``tf_op`` stat is this same name stack, so
``benchmark/tools/scope_table.py`` finds on the chip what is found here.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

import fedml_tpu as fedml
from fedml_tpu import data as data_mod
from fedml_tpu import models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.core.mlops import scopes
from fedml_tpu.parallel.sharding import make_mesh
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import TransformerConfig
from fedml_tpu.simulation.sp_api import FedAvgAPI


def scope_names(lowered) -> set:
    """Every element of every name stack in the lowered program's locations,
    with JAX's transform wrappers (``jvp(..)``, ``transpose(..)``,
    ``vmap(..)``) peeled off."""
    names = set()
    for stack in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)):
        for part in stack.split("/"):
            while True:
                m = re.fullmatch(r"\w+\((.*)\)", part)
                if not m:
                    break
                part = m.group(1)
            names.add(part)
    return names


# ---------------------------------------------------------------------------
# the Cheetah step
# ---------------------------------------------------------------------------


def lowered_step(accum_steps: int, **cfg):
    config = TransformerConfig.tiny()
    if cfg:
        import dataclasses

        config = dataclasses.replace(config, **cfg)
    trainer = CheetahTrainer(config, make_mesh(None), accum_steps=accum_steps)
    state = trainer.init_state(jax.random.PRNGKey(0))
    shape = (8, 32) if accum_steps == 1 else (accum_steps, 8, 32)
    tokens = jnp.zeros(shape, jnp.int32)
    return trainer.lower_step(state, tokens, jnp.ones_like(tokens))


@pytest.mark.parametrize("accum_steps, absent", [(2, set()), (1, {"grad_accum"})])
def test_train_step_carries_its_vocabulary(accum_steps, absent):
    names = scope_names(lowered_step(accum_steps))
    # the tiny preset has none of the mechanisms TRAIN_STEP_BLOCKS names
    assert set(scopes.TRAIN_STEP) - names == absent | set(scopes.TRAIN_STEP_BLOCKS)
    # flax's modules and JAX's transforms name the model; they are not doubled
    assert {"Attention_0", "FeedForward_0", "RMSNorm_0"} <= names
    assert not {"attention", "feed_forward", "forward", "backward"} & names


def test_train_step_names_what_a_configuration_adds_to_the_blocks():
    """Latent attention, hyper-connections, a sigmoid-routed expert layer with
    a shared expert and the MTP module, each under its own name."""
    names = scope_names(lowered_step(
        1, attn_kind="mla", q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, hc_mult=2,
        hc_sinkhorn_iters=2, moe_experts=4, moe_top_k=2, moe_router="sigmoid",
        moe_capacity_factor=0.0, moe_d_ff=32, moe_shared_experts=1,
        first_k_dense=1, mtp_layers=1))
    kda = {"kda", "kda_conv", "kda_gate", "kda_chunk"}  # no such layer here
    bd = {"qk_norm", "bd_noise"}   # nor q/k norms or the block-diffusion draw
    ssd = {"mamba", "ssd_proj", "ssd_conv", "ssd_chunk", "ssd_norm"}
    conv = {"shortconv", "shortconv_proj", "shortconv_conv"}
    window = {"window_attention"}
    assert set(scopes.TRAIN_STEP) - names == (
        {"grad_accum"} | kda | bd | ssd | conv | window)
    assert {"LatentAttention_0", "HyperConnection_0", "MoEFeedForward_0",
            "FeedForward_0", "mtp"} <= names


def test_train_step_names_the_linear_mixer_beside_latent_attention():
    """A stack whose mixer is chosen per layer: the KDA layers' scopes and
    the latent-attention layer's, each under its module."""
    names = scope_names(lowered_step(
        1, n_layers=2, attn_kind="mla", kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, layer_group_size=2,
        kda_head_dim=16))
    assert {"kda", "kda_conv", "kda_gate", "kda_chunk", "mla",
            "KimiDeltaAttention_0", "LatentAttention_0"} <= names


def test_train_step_names_the_block_diffusion_draw_and_the_qk_norms():
    """The block-diffusion objective over a GQA stack with per-head q/k norms:
    the draw with the doubled input, and the norms inside their module."""
    names = scope_names(lowered_step(
        1, attn_head_dim=16, qk_norm=True, objective="block_diffusion",
        bd_block=4, bd_mask_token=255, moe_experts=4, moe_top_k=2,
        moe_capacity_factor=0.0, moe_d_ff=32))
    assert {"bd_noise", "qk_norm", "moe_route", "moe_experts", "loss",
            "Attention_0", "q_norm", "k_norm"} <= names


def test_train_step_names_the_state_space_mixer_and_its_parts():
    """A layer list given as a pattern, one sublayer a layer: the mixer's
    scopes under its module, the expert layer and attention without a rotation
    beside it, and no stale name in the compiled step's map."""
    lowered = lowered_step(
        1, n_layers=4, n_kv_heads=2, pos_emb="none", layer_pattern="ME*-",
        ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=16, ssm_chunk=16,
        ffn_act="relu2", moe_experts=4, moe_top_k=2, moe_router="sigmoid",
        moe_capacity_factor=0.0, moe_d_ff=32, moe_shared_experts=1,
        moe_shared_d_ff=48)
    names = scope_names(lowered)
    assert {"mamba", "ssd_proj", "ssd_conv", "ssd_chunk", "ssd_norm",
            "Mamba2Mixer_0", "MoEFeedForward_0", "Attention_0",
            "FeedForward_0", "moe_route", "shared_expert"} <= names
    assert "rope" not in names
    # each block holds one norm: one sublayer a layer
    assert "RMSNorm_1" not in names


def test_train_step_names_the_short_convolution_and_its_parts():
    """A mixer list under the published names (ISSUE 40): the gated short
    convolution's scopes under its module, attention with q/k norms beside
    it, and the tied head's loss."""
    lowered = lowered_step(
        1, n_layers=2, layer_types=("conv", "full_attention"),
        first_k_dense=1, qk_norm=True, tie_embeddings=True, moe_experts=4,
        moe_top_k=2, moe_router="sigmoid", moe_capacity_factor=0.0,
        moe_d_ff=32)
    names = scope_names(lowered)
    assert {"shortconv", "shortconv_proj", "shortconv_conv",
            "ShortConvMixer_0", "Attention_0", "qk_norm", "rope",
            "moe_route", "loss"} <= names
    assert "mamba" not in names and "shared_expert" not in names


def test_train_step_names_the_window_layers_attention_alone():
    """Window layers beside a full one under the published names: the
    attention over a window's band under ``window_attention`` inside that
    layer's module, the full layer's attention under no such name."""
    lowered = lowered_step(
        1, n_layers=3, layer_types=("sliding_attention", "full_attention",
                                    "sliding_attention"), attn_window=8)
    stacks = re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True))
    windowed = {re.search(r"Block_(\d+)", s).group(1) for s in stacks
                if "/window_attention" in s}
    assert windowed == {"0", "2"}
    assert "window_attention" in scope_names(lowered)


def test_train_step_learned_positions_are_embed_and_rope():
    names = scope_names(lowered_step(1, pos_emb="learned"))
    assert {"embed", "rope"} <= names


# ---------------------------------------------------------------------------
# the FedAvg round
# ---------------------------------------------------------------------------

ALWAYS = {"local_train", "loss", "optimizer", "aggregate", "metrics"}


def make_api(**kw):
    base = dict(dataset="synthetic", model="lr", client_num_in_total=8,
                client_num_per_round=8, comm_round=2, epochs=1, batch_size=16,
                learning_rate=0.1, frequency_of_the_test=100)
    base.update(kw)
    args = fedml.init(Arguments(overrides=base), should_init_logs=False)
    ds, od = data_mod.load(args)
    return FedAvgAPI(args, fedml.get_device(args), ds,
                     model_mod.create(args, od))


def lowered_round(api):
    api._setup_round()
    if api._superround_step is not None:
        return api._superround_step.lower(api._round_state(), jnp.int32(0))
    return api._round_step.lower(*api._round_inputs(0))


@pytest.mark.parametrize("config, built", [
    (dict(), set()),
    (dict(federated_optimizer="FedProx"), set()),
    (dict(federated_optimizer="FedOpt", server_optimizer="adam",
          server_lr=0.03), {"server_update"}),
    (dict(federated_optimizer="FedNova"), {"server_update"}),
    (dict(federated_optimizer="FedSGD"), {"server_update"}),
    (dict(federated_optimizer="SCAFFOLD"), {"select_cohort", "server_update"}),
    (dict(superround_k=2), {"select_cohort"}),
    (dict(enable_dp=True, dp_type="cdp", mechanism_type="gaussian",
          epsilon=5.0, delta=1e-5, sensitivity=1.0), {"dp"}),
    (dict(enable_dp=True, dp_type="ldp", mechanism_type="gaussian",
          epsilon=5.0, delta=1e-5, sensitivity=1.0), {"dp"}),
    (dict(enable_attack=True, attack_type="byzantine_random",
          byzantine_client_num=2, enable_defense=True,
          defense_type="multikrum", krum_param_m=4),
     {"attack", "defense"}),
])
def test_round_carries_its_vocabulary(config, built):
    """Every round has the base names; the rest appear exactly where the
    configuration builds them, and together they are the whole vocabulary."""
    try:
        api = make_api(**config)
        names = scope_names(lowered_round(api))
    finally:
        # the attacker and defender are process singletons: leave them off
        make_api()
    expect = (ALWAYS - {"optimizer"} if config.get("federated_optimizer")
              == "FedSGD" else ALWAYS) | built
    assert names & set(scopes.ROUND) == expect


def test_round_configurations_cover_the_vocabulary():
    covered = set(ALWAYS)
    for mark in test_round_carries_its_vocabulary.pytestmark:
        if mark.name == "parametrize":
            for _, built in mark.args[1]:
                covered |= built
    assert covered == set(scopes.ROUND)


def test_evaluate_carries_its_vocabulary(monkeypatch):
    """The evaluation program is a jitted closure of ``make_eval_fn``: catch
    it as it is built and lower it on a test batch."""
    from fedml_tpu.ml.evaluate import make_eval_fn

    api = make_api()
    built, real_jit = [], jax.jit

    def spy(fn, *args, **kw):
        built.append(real_jit(fn, *args, **kw))
        return built[-1]

    monkeypatch.setattr(jax, "jit", spy)
    make_eval_fn(api.bundle)
    monkeypatch.undo()
    (eval_batch,) = built
    x, y = api.ds.test_x[:4], api.ds.test_y[:4]
    lowered = eval_batch.lower(api.global_params, jnp.asarray(x),
                               jnp.asarray(y), jnp.ones((4,), jnp.float32))
    assert set(scopes.EVALUATE) <= scope_names(lowered)


# ---------------------------------------------------------------------------
# the vocabulary is closed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("vocabulary", [scopes.TRAIN_STEP, scopes.ROUND,
                                        scopes.EVALUATE])
def test_a_scope_outside_the_vocabulary_fails(vocabulary):
    with scopes.scope(vocabulary, vocabulary[0]):
        pass
    with pytest.raises(ValueError, match="not in the program's vocabulary"):
        scopes.scope(vocabulary, "forward")
