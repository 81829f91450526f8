"""The program says which scope every instruction of its compiled step
belongs to (ISSUE 36).

``scopes.scope_key`` cuts one name stack down to ``(scope path, pass)`` and
must give ``benchmark/tools/scope_table.py``'s answers; ``scopes.program_map``
does so for every instruction of a compiled program's optimized HLO; with
tracking on, the Cheetah step and the FedAvg round each publish that map once
per compiled program (``program_scopes``), compiling nothing to do so, and
with tracking off they do nothing but check one bool.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import fedml_tpu as fedml
from fedml_tpu import data as data_mod
from fedml_tpu import models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.core import mlops
from fedml_tpu.core.mlops import scopes, telemetry
from fedml_tpu.parallel.sharding import make_mesh
from fedml_tpu.parallel.train_step import CheetahTrainer
from fedml_tpu.parallel.transformer import TransformerConfig
from fedml_tpu.runner import FedMLRunner

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name, *parts):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(autouse=True)
def clean_state():
    """Each test gets a fresh registry and a closed sink."""
    telemetry.registry().reset()
    yield
    mlops.close()
    telemetry.registry().reset()
    telemetry._State.enabled = False
    mlops.MLOpsStore.enabled = False
    mlops.MLOpsStore.jsonl_path = None


# ---------------------------------------------------------------------------
# the cut: one name stack -> (scope path, pass)
# ---------------------------------------------------------------------------

TOOL = _load("scope_table", "benchmark", "tools", "scope_table.py")
# the stacks the tool's own test holds it to, with the tool's answers
STACKS = _load(
    "host_spans_tests", "tests", "benchmark", "test_benchmark_host_spans.py"
).test_scope_key_cuts_the_stack_at_vocabulary_and_module.pytestmark[0].args[1]
VOCABULARY = TOOL.vocabulary()


@pytest.mark.parametrize("stack, want", STACKS)
@pytest.mark.parametrize("layers", [False, True])
def test_scope_key_gives_the_tools_answers(stack, want, layers):
    got = scopes.scope_key(stack, VOCABULARY, layers)
    tool = TOOL.scope_key(stack, VOCABULARY, layers)
    assert got == (tool[0].replace(TOOL.NO_NAME, ""), tool[1])
    if not layers:
        assert got == (want[0].replace(TOOL.NO_NAME, ""), want[1])
    # the compiled program's op_name is the trace's tf_op less its colon
    assert scopes.scope_key(stack.rstrip(":"), VOCABULARY, layers) == got


def test_a_nested_pjit_and_an_empty_stack_are_no_scope():
    assert scopes.scope_key("jit(f)/pjit(optimizer)/mul", ("optimizer",)) == ("", "fwd")
    assert scopes.scope_key("", ("optimizer",)) == ("", "fwd")
    assert scopes.scope_key("jit(f)/optimizer", ("optimizer",)) == ("", "fwd")


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

FileNames
1 "x.py"

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sin.1 = f32[8]{0} sine(%param_0), metadata={op_name="jit(step)/jvp(Model)/Dense_0/sin"}
}

%region_0.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %cosine.3 = f32[8]{0} cosine(%get-tuple-element.1), metadata={op_name="jit(step)/transpose(jvp(Model))/jvp(Model)/checkpoint/rematted_computation/Dense_0/cos"}
  %negate.4 = f32[8]{0} negate(%cosine.3), metadata={op_name="jit(step)/transpose(jvp(Model))/Dense_0/neg"}
  %copy.9 = f32[8]{0} copy(%negate.4)
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%get-tuple-element.1, %copy.9)
}

ENTRY %main.9 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %sine_fusion = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/jvp(Model)/Dense_0/sin"}
  %while.7 = (s32[], f32[8]{0}) while(%tuple.6), condition=%region_1.3, body=%region_0.2, metadata={op_name="jit(step)/transpose(jvp(loss))/old_scope/while"}
  %splash_fwd.2 = f32[8]{0} custom-call(%sine_fusion), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512, \\"op_name\\": \\"not this one\\"}"
}}, metadata={op_name="jit(step)/jvp(Model)/Attention_0/vmap(jit(_splash))/splash_fwd/pallas_call" stack_frame_id=5}, backend_config={"x":1}
  ROOT %multiply.8 = f32[8]{0} multiply(%sine_fusion, %sine_fusion), metadata={op_name="jit(step)/optimizer/clip/mul"}
}
"""


def test_program_map_of_a_module_written_by_hand():
    found = scopes.program_map(HLO, ("optimizer", "clip", "loss"))
    assert found["module"] == "jit_step"
    keys = [tuple(k) for k in found["scopes"]]
    of = {name: keys[i] for name, i in found["ops"].items()}
    # the while's body and the entry, not the fused computation's inside
    assert "sin.1" not in of and "param_0" not in of
    assert of["sine_fusion"] == ("Model/Dense", "fwd")
    assert of["cosine.3"] == ("Model/Dense", "remat")
    assert of["negate.4"] == ("Model/Dense", "bwd")
    assert of["while.7"] == ("loss", "bwd") and of["Arg_0.1"] == ("", "fwd")
    # what XLA itself put into the loop's body takes the loop's name stack
    assert of["copy.9"] == of["get-tuple-element.1"] == ("loss", "bwd")
    assert of["multiply.8"] == ("optimizer/clip", "fwd")
    # a Mosaic call's text runs over three lines, its metadata on the last
    assert of["splash_fwd.2"] == ("Model/Attention", "fwd")
    # parameters, tuples and their elements compute nothing and count nowhere
    assert (found["instructions"], found["unnamed"]) == (7, 0)
    assert found["stale"] == ["old_scope"]  # not the kernel's own name
    assert scopes.program_map(HLO, ("optimizer", "clip", "loss"), layers=True)[
        "scopes"][found["ops"]["sine_fusion"]] == ["Model/Dense_0", "fwd"]
    assert len(json.dumps(found)) < 1200


# ---------------------------------------------------------------------------
# compiled programs, small, on the CPU
# ---------------------------------------------------------------------------

EXPERTS = dict(moe_experts=4, moe_top_k=2, moe_router="sigmoid",
               moe_capacity_factor=0.0, moe_d_ff=32, moe_shared_experts=1,
               first_k_dense=1)
# the share of a program's computing instructions under no scope, by count, at
# these sizes: XLA:CPU leaves copies, converts and an unrolled threefry bare.
# (By device time it is 2.5% in the Mistral cell: PERF.md.)
UNNAMED_LIMIT = 0.5


def instruction_names(text: str) -> set:
    """Every instruction name outside the fused computations, read apart from
    ``program_map``: computation by computation."""
    fused = set(re.findall(r" fusion\(.*calls=%?([^\s,}]+)", text))
    names = set()
    for block in re.split(r"\n(?=(?:ENTRY )?%?\S+ \(.*\) -> .* \{\n)", text):
        header = re.match(r"(?:ENTRY )?%?(\S+) \(", block)
        if header and header.group(1) not in fused:
            names |= set(re.findall(r"^\s+(?:ROOT )?%?(\S+) = ", block, re.M))
    return names


@pytest.mark.parametrize("config, holds, passes", [
    (dict(), {"optimizer", "loss", "embed"}, {"fwd", "bwd"}),
    (EXPERTS, {"optimizer", "loss", "moe_experts", "moe_route",
               "moe_products", "shared_expert"}, {"fwd", "bwd"}),
    (dict(remat=True), {"optimizer", "loss", "rope"}, {"fwd", "bwd", "remat"}),
    # a layer list given as a pattern, one sublayer a layer (ISSUE 38): the
    # state-space mixer's scopes, every instruction of the step still mapped
    (dict(remat=True, n_layers=3, pos_emb="none", layer_pattern="ME*",
          ssm_heads=4, ssm_head_dim=16, ssm_groups=2, ssm_state=16,
          ssm_chunk=16, ffn_act="relu2", moe_experts=4, moe_top_k=2,
          moe_router="sigmoid", moe_capacity_factor=0.0, moe_d_ff=32,
          moe_shared_experts=1, moe_shared_d_ff=48),
     {"mamba", "ssd_proj", "ssd_conv", "ssd_chunk", "ssd_norm", "Mamba2Mixer",
      "moe_experts", "shared_expert"}, {"fwd", "bwd", "remat"}),
    # a mixer list under the published names and a tied head (ISSUE 40)
    (dict(remat=True, n_layers=2, layer_types=("conv", "full_attention"),
          first_k_dense=1, qk_norm=True, tie_embeddings=True, moe_experts=4,
          moe_top_k=2, moe_router="sigmoid", moe_capacity_factor=0.0,
          moe_d_ff=32),
     {"shortconv", "shortconv_proj", "shortconv_conv", "ShortConvMixer",
      "qk_norm", "moe_experts"}, {"fwd", "bwd", "remat"}),
], ids=["dense", "experts", "remat", "state_space", "short_conv"])
def test_compiled_step_maps_every_instruction(config, holds, passes):
    cfg = dataclasses.replace(TransformerConfig.tiny(), **config)
    trainer = CheetahTrainer(cfg, make_mesh(None))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((8, 32), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).compile().as_text()
    found = scopes.program_map(text, scopes.TRAIN_STEP)
    assert found["module"] == "jit__train_step_raw"
    names = instruction_names(text)
    assert len(names) > 300 and names == set(found["ops"])
    elements = {e for path, _ in found["scopes"] for e in path.split("/")}
    assert holds <= elements and "Transformer" in elements
    assert {which for _, which in found["scopes"]} == passes
    assert 0 < found["unnamed"] < UNNAMED_LIMIT * found["instructions"]
    # with today's vocabulary nothing reads as a scope of another tree's
    assert not set(found["stale"]) & set(scopes.TRAIN_STEP)
    renamed = tuple(n for n in scopes.TRAIN_STEP if n != "optimizer")
    assert "optimizer" in scopes.program_map(text, renamed)["stale"]


def test_an_expert_layers_backward_and_recomputation_stay_under_its_scopes():
    """ISSUE 37 wrote the combine's backward by hand (a gather from the tokens'
    cotangent, the product with the rows' gates, the gate gradient's reduction
    and the sort that returns it to ``[T, k]``): every instruction of it is
    traced under ``moe_experts`` like the forward's, so the seconds
    ``moe_experts.dispatch_combine_s_per_step`` reads hold all of it. Only the
    forward has instructions under the module alone (the sown counters). The
    grouped products stand beside it under ``moe_products``, in
    every pass, and none of their instructions under ``moe_experts``."""
    cfg = dataclasses.replace(TransformerConfig.tiny(), remat=True, **EXPERTS)
    # one device: under the tests' eight the module's own reshape of the
    # tokens becomes a collective of the backward pass too
    trainer = CheetahTrainer(cfg, make_mesh({"fsdp": 1}, devices=jax.devices()[:1]))
    state = trainer.init_state(jax.random.PRNGKey(0))
    tokens = jnp.zeros((8, 32), jnp.int32)
    text = trainer.lower_step(state, tokens, jnp.ones_like(tokens)).compile().as_text()
    found = scopes.program_map(text, scopes.TRAIN_STEP)
    of = {name: tuple(found["scopes"][i]) for name, i in found["ops"].items()}
    layer = {name: key for name, key in of.items()
             if "MoEFeedForward" in key[0].split("/")}
    inner = {"moe_route", "moe_experts", "moe_products", "shared_expert"}
    bare = {which for path, which in layer.values()
            if not inner & set(path.split("/"))}
    assert bare == {"fwd"}
    passes = {which for path, which in layer.values()
              if path.endswith("/moe_experts")}
    assert passes == {"fwd", "bwd", "remat"}
    products = {name: key for name, key in layer.items()
                if "moe_products" in key[0].split("/")}
    assert {which for _, which in products.values()} == {"fwd", "bwd", "remat"}
    assert not any("moe_experts" in path.split("/")
                   for path, _ in products.values())
    # on this CPU the products are ``ragged_dot``'s lowering, dots among it;
    # dispatch and combine hold none
    dots = set(re.findall(r"^\s+%?(\S+) = .* dot\(", text, re.M))
    assert dots & set(products)
    assert not any(layer[name][0].endswith("/moe_experts")
                   for name in dots & set(layer))
    # the one sort of the backward pass is the gate gradient's way back
    sorts = [name for name in re.findall(r"^\s+%?(\S+) = .* sort\(", text, re.M)
             if layer.get(name, ("", ""))[1] == "bwd"]
    assert len(sorts) == 1
    assert layer[sorts[0]][0].endswith("MoEFeedForward/moe_experts")


# ---------------------------------------------------------------------------
# the publication: once per compiled program, tracked runs only
# ---------------------------------------------------------------------------


def events(kind):
    return [e for e in mlops.read_events() if e.get("kind") == kind]


def cheetah_runner(tmp_path, tracked=True, **kw):
    base = dict(training_type="distributed", dataset="synthetic",
                model="transformer", model_size="tiny", total_steps=3,
                batch_size=8, seq_len=32, enable_tracking=tracked,
                tracking_dir=str(tmp_path), run_id="scopes")
    base.update(kw)
    args = fedml.init(Arguments(overrides=base), should_init_logs=False)
    return FedMLRunner(args, fedml.get_device(args), None, None)


def fedavg_runner(tmp_path, tracked=True):
    args = fedml.init(Arguments(overrides=dict(
        training_type="simulation", dataset="synthetic", model="lr",
        client_num_in_total=8, client_num_per_round=4, comm_round=3, epochs=1,
        batch_size=16, learning_rate=0.1, frequency_of_the_test=1000,
        enable_tracking=tracked, tracking_dir=str(tmp_path), run_id="scopes",
    )), should_init_logs=False)
    ds, od = data_mod.load(args)
    return FedMLRunner(args, fedml.get_device(args), ds, model_mod.create(args, od))


@pytest.mark.parametrize("mesh_shape", ["", "fsdp:8"])
def test_two_runs_of_a_cheetah_runner_publish_each_program_once(tmp_path, mesh_shape):
    runner = cheetah_runner(tmp_path, mesh_shape=mesh_shape)
    runner.run()
    compiles = telemetry.compiles()
    published = events("program_scopes")
    # where a mesh shards the state, step 0's program (the moments arrive
    # replicated) is not the later steps': one event each
    compiled = [e for e in events("compile")
                if e["fun_name"] == "jit(_train_step_raw)"]
    assert len(published) == len(compiled) >= (2 if mesh_shape else 1)
    for e in published:
        assert (e["program"], e["module"]) == ("_train_step_raw", "jit__train_step_raw")
        assert e["instructions"] > e["unnamed"] > 0 and e["stale"] == []
        assert max(e["ops"].values()) == len(e["scopes"]) - 1
        assert ["optimizer", "fwd"] in e["scopes"] and ["loss", "bwd"] in e["scopes"]
    assert telemetry.registry().counter("program_scopes.skipped") == 0
    runner.run()  # the benchmark's window: the same runner, called again
    assert telemetry.compiles() == compiles
    assert events("program_scopes") == published
    # what the publication costs stands in the record of the step it ran in
    first = [r for r in events("round_record") if r["round_idx"] == 0][0]
    spans = {s["name"]: s for s in first["spans"]}
    assert spans["program_scopes"]["parent"] == spans["step"]["span"]
    assert "program_scopes" not in first["phases"]


def test_two_runs_of_a_fedavg_runner_publish_the_round_once(tmp_path):
    runner = fedavg_runner(tmp_path)
    runner.run()
    compiles = telemetry.compiles()
    published = events("program_scopes")
    assert len(published) == 1
    assert len([e for e in events("compile") if e["fun_name"] == "jit(core)"]) == 1
    e = published[0]
    assert (e["program"], e["module"]) == ("core", "jit_core")
    paths = {path for path, _ in e["scopes"]}
    assert {"local_train/optimizer", "local_train/loss", "aggregate"} <= paths
    assert ["local_train/LogisticRegression/Dense", "bwd"] in e["scopes"]
    runner.run()
    assert telemetry.compiles() == compiles
    assert events("program_scopes") == published
    first = [r for r in events("round_record") if r["round_idx"] == 0][0]
    spans = {s["name"]: s for s in first["spans"]}
    assert spans["program_scopes"]["parent"] == spans["dispatch"]["span"]


class NeverLowered:
    """A jitted function that may be called and must not be lowered."""

    def __init__(self, jitted):
        self.jitted = jitted

    def __call__(self, *args):
        return self.jitted(*args)

    def lower(self, *args):
        raise AssertionError("an untracked run lowered its program")


def refuse(*args, **kwargs):
    raise AssertionError("an untracked run did more than check one bool")


@pytest.mark.parametrize("loop", ["cheetah", "fedavg"])
def test_untracked_loops_check_one_bool_and_nothing_else(tmp_path, monkeypatch, loop):
    for name in ("compiles", "abstract_of", "record_program_scopes"):
        monkeypatch.setattr(telemetry, name, refuse)
    if loop == "cheetah":
        runner = cheetah_runner(tmp_path, tracked=False)
        trainer = runner.runner.trainer
        trainer._step_jit = NeverLowered(trainer._step_jit)
    else:
        runner = fedavg_runner(tmp_path, tracked=False)
        api = runner.runner.fl_trainer
        api._setup_round()
        api._round = api._round_step = NeverLowered(api._round_step)
    runner.run()
    assert mlops.read_events() == []


def test_a_lowering_that_is_not_the_calls_is_not_compiled_to_be_named(tmp_path):
    cheetah_runner(tmp_path, total_steps=1)  # tracking on, a sink to write to
    step = jax.jit(lambda x: jnp.sin(x) * 2.0)
    called, other = jnp.ones((8,)), jnp.ones((16,))
    step(called)
    compiles = telemetry.compiles()
    telemetry.record_program_scopes(step.lower(other), ())
    assert telemetry.compiles() == compiles
    assert events("program_scopes") == []
    assert telemetry.registry().counter("program_scopes.skipped") == 1
    telemetry.record_program_scopes(
        step.lower(*telemetry.abstract_of((called,))), ())
    assert telemetry.compiles() == compiles
    assert [e["program"] for e in events("program_scopes")] == ["_lambda"]  # the module is jit__lambda


def test_emit_record_is_a_span_of_its_own_inside_hooks(tmp_path):
    cheetah_runner(tmp_path).run()
    records = events("round_record")
    assert len(records) == 3
    for r in records:
        spans = {s["name"]: s for s in r["spans"]}
        assert spans["emit_record"]["parent"] == spans["hooks"]["span"]
        assert "emit_record" not in r["phases"] and "hooks" in r["phases"]
        lo, hi = spans["hooks"]["ts_ns"], spans["hooks"]["ts_ns"] + spans["hooks"]["dur_ns"]
        assert lo <= spans["emit_record"]["ts_ns"] <= hi
