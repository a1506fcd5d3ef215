"""Region scopes (seist_tpu/obs/scopes.py): op_name -> region and pass, the
HLO parser, the scope map of a step built by the program's own factories,
and the vocabulary held against every registered model family."""

import contextlib
from types import SimpleNamespace

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import seist_tpu
from seist_tpu import taskspec
from seist_tpu.models import api
from seist_tpu.obs import scopes
from seist_tpu.train import create_train_state, jit_step, make_train_step
from seist_tpu.train.state import TrainState

NO_TRANSFORMS = SimpleNamespace(
    targets_transform_for_loss=None, outputs_transform_for_loss=None
)


@pytest.mark.parametrize("op_name, region, which", [
    ("", "unowned", ""),
    ("jit(guarded_call)/while/body/closed_call/dynamic_slice", "unowned", ""),
    ("jit(call)/while/body/closed_call/device_aug/vmap(jit(_uniform))/mul",
     "device_aug", ""),
    ("jit(call)/while/body/closed_call/cache_gather/gather", "cache_gather", ""),
    ("jit(train_step)/jvp(loss)/reduce_sum", "loss", "fwd"),
    ("jit(train_step)/transpose(jvp(loss))/mul", "loss", "bwd"),
    ("jit(train_step)/optimizer/jit(_where)/select_n", "optimizer", ""),
    ("jit(s)/jvp(model)/SeismogramTransformer/_backbone/stem2/conv3/erfc",
     "stem", "fwd"),
    ("jit(s)/transpose(jvp(model))/S/_backbone/stage1_block0/msmc/conv1/mlp/mul",
     "msmc", "bwd"),
    ("jit(s)/jvp(model)/S/_backbone/stage3_block1/gconv_path/gconv/conv/add",
     "msmc", "fwd"),
    ("jit(s)/jvp(model)/S/_backbone/stage3_block1/attn_path/attention/q_proj/dot_general",
     "attention", "fwd"),
    ("jit(s)/transpose(jvp(model))/S/_backbone/stage3_block1/mlp_path/mlp/lin0/dot_general",
     "mlp", "bwd"),
    ("jit(s)/jvp(model)/S/_backbone/stage2_aggr/proj/dot_general",
     "stage_aggr", "fwd"),
    ("jit(s)/jvp(model)/S/_head/out_head/conv0/conv_general_dilated",
     "head", "fwd"),
    ("jit(s)/jvp(model)/PhaseNet/down3/bn1/mul", "conv_down", "fwd"),
    ("jit(s)/transpose(jvp(model))/PhaseNet/up0/convt/conv_general_dilated",
     "conv_up", "bwd"),
    ("jit(s)/jvp(model)/EQTransformer/encoder/bilstm/fwd/while/body/add",
     "lstm", "fwd"),
    ("jit(s)/OptimizedLSTMCell_0.scan_fn/OptimizedLSTMCell_0/concatenate",
     "lstm", ""),
    ("jit(s)/jvp(model)/convert_element_type", "model_other", "fwd"),
    ("jit(eval_step)/model/PhaseNet/conv_in/conv_general_dilated",
     "model_other", ""),
])
def test_classify(op_name, region, which):
    assert scopes.classify(op_name) == (region, which)


def test_regions_are_ordered_and_compile():
    names = [name for name, _ in scopes.REGIONS]
    assert len(names) == len(set(names)) and scopes.UNOWNED not in names
    for wanted in ("device_aug", "cache_gather", "stem", "msmc", "attention",
                   "mlp", "head", "conv_down", "conv_up", "lstm", "loss",
                   "optimizer"):
        assert wanted in names
    assert names[-1] == "model_other"  # the catch-all comes last


HLO = """HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %convert.3 = bf16[8]{0} convert(%param_0.1), metadata={op_name="jit(step)/jvp(model)/convert_element_type"}
  ROOT %convert.4 = f32[8]{0} convert(%convert.3)
}

%region_add.2 (a.1: f32[], b.1: f32[]) -> f32[] {
  %a.1 = f32[] parameter(0)
  %b.1 = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a.1, %b.1), metadata={op_name="jit(step)/jvp(loss)/reduce_sum"}
}

%body.3 (t.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t.1), index=1
  %copy.7 = f32[8]{0} copy(%gte.1)
  %mul.2 = f32[8]{0} multiply(%copy.7, %copy.7), metadata={op_name="jit(step)/device_aug/vmap(while)/body/mul"}
  %gte.0 = s32[] get-tuple-element(%t.1), index=0
  ROOT %tuple.4 = (s32[], f32[8]{0}) tuple(%gte.0, %mul.2)
}

%cond.3 (t.2: (s32[], f32[8])) -> pred[] {
  %t.2 = (s32[], f32[8]{0}) parameter(0)
  %gte.3 = s32[] get-tuple-element(%t.2), index=0
  %c.1 = s32[] constant(4)
  ROOT %lt.1 = pred[] compare(%gte.3, %c.1), direction=LT
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %fusion.5 = f32[8]{0:T(8,128)(2,1)} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  %copy.11 = f32[8]{0} copy(%fusion.5)
  %c.0 = s32[] constant(0)
  %tuple.1 = (s32[], f32[8]{0}) tuple(%c.0, %copy.11)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond.3, body=%body.3, metadata={op_name="jit(step)/device_aug/vmap(while)"}
  %gte.9 = f32[8]{0} get-tuple-element(%while.1), index=1
  %reduce.1 = f32[] reduce(%gte.9, %c.0), dimensions={0}, to_apply=%region_add.2, metadata={op_name="jit(step)/transpose(jvp(loss))/reduce_sum"}
  ROOT %add.1 = f32[8]{0} add(%gte.9, %gte.9), metadata={op_name="jit(step)/optimizer/add"}
}
"""


def test_parse_hlo_names_what_runs():
    m = scopes.parse_hlo(HLO)
    # fused bodies, reducers, parameters, constants and tuple plumbing are out
    assert set(m) == {"%copy.7", "%mul.2", "%lt.1", "%fusion.5", "%copy.11",
                      "%while.1", "%reduce.1", "%add.1"}
    # a fusion without metadata takes its fused computation's op_name
    assert m["%fusion.5"]["region"] == "model_other"
    assert m["%fusion.5"]["pass"] == "fwd"
    # no op_name, in the entry computation: unowned, not spread
    assert m["%copy.11"] == {"region": "unowned", "pass": "", "op_name": ""}
    # no op_name, inside a loop a region owns: that region's (containment)
    assert m["%copy.7"]["region"] == "device_aug"
    assert m["%lt.1"]["region"] == "device_aug"
    assert m["%mul.2"]["region"] == "device_aug"
    assert (m["%reduce.1"]["region"], m["%reduce.1"]["pass"]) == ("loss", "bwd")
    assert m["%add.1"]["region"] == "optimizer"


class Tiny(nn.Module):
    @nn.compact
    def __call__(self, x, train: bool):
        x = nn.relu(nn.Dense(16, name="stem0")(x))
        return nn.Dense(4, name="out_head")(x)


def tiny_step(guard=True):
    model = Tiny()
    x = jnp.ones((8, 32))
    y = jnp.zeros((8, 4))
    key = jax.random.PRNGKey(0)
    state = TrainState.create(
        apply_fn=model.apply, params=model.init(key, x, train=False)["params"],
        tx=optax.adam(1e-3), batch_stats=None,
    )
    step = jit_step(
        make_train_step(NO_TRANSFORMS, lambda o, t: jnp.mean((o - t) ** 2),
                        guard=guard),
        donate_state=False,
    )
    return step, (state, x, y, key)


def executing_instructions(text):
    """Independent of parse_hlo's bookkeeping: every instruction line whose
    computation no fusion ``calls=`` and no reducer ``to_apply=`` names."""
    import re

    inlined = set(re.findall(r"fusion\(.*calls=(%[^\s,}]+)", text))
    inlined |= {c for line in text.splitlines() if " call(" not in line
                for c in re.findall(r"to_apply=(%[^\s,}]+)", line)}
    names, current = [], None
    for line in text.splitlines():
        head = scopes._COMPUTATION.match(line)
        if head:
            current = head.group(1)
        m = scopes._INSTRUCTION.match(line)
        if m and current not in inlined:
            op = scopes._OPCODE.search(" " + m.group(3))
            if (op.group(1) if op else "") not in scopes._NOT_OPS:
                names.append(m.group(2))
    return names


def test_scope_map_of_a_small_step():
    step, args = tiny_step()
    assert scopes.scope_map(step) is None  # never called: nothing to lower
    step(*args)
    assert step.first_call_types is not None and step.jitted is not None
    text = scopes.hlo_text(step)
    assert 'op_name="' in text
    m = scopes.scope_map(step)
    found = {(v["region"], v["pass"]) for v in m.values()}
    assert {("stem", "fwd"), ("stem", "bwd"), ("head", "fwd"), ("head", "bwd"),
            ("optimizer", "")} <= found
    assert any(r == "loss" for r, _ in found)
    # every instruction that runs is named, once
    names = executing_instructions(text)
    assert len(names) == len(set(names))
    assert set(names) == set(m)


def test_the_map_costs_nothing_until_asked(monkeypatch):
    """With tracing off the step's call path is a plain call of the jitted
    function: nothing is printed, nothing parsed."""
    step, args = tiny_step()
    asked = []
    monkeypatch.setattr(scopes, "hlo_text", lambda s: asked.append(s))
    step(*args)
    step(*args)
    assert asked == []


def _null_scope(_name):
    return contextlib.nullcontext()


def test_named_scopes_change_metadata_only(monkeypatch):
    """The lowered program without debug information is the same text with
    the region scopes and without them, and so is JAX's compile-cache key
    (``jax_compilation_cache_include_metadata_in_key`` is off)."""
    from jax._src import cache_key, compiler

    assert not jax.config.jax_compilation_cache_include_metadata_in_key

    def lowered_text_and_key():
        step, args = tiny_step()
        lowered = step.jitted.lower(*args)
        backend = jax.devices()[0].client
        options = compiler.get_compile_options(
            num_replicas=1, num_partitions=1)
        key = cache_key.get(
            lowered.compiler_ir(), np.array(jax.devices()[:1]), options, backend)
        return lowered.as_text(), key

    with_scopes = lowered_text_and_key()
    monkeypatch.setattr(jax, "named_scope", _null_scope)
    without = lowered_text_and_key()
    assert with_scopes[0] == without[0]
    assert with_scopes[1] == without[1]


FAMILIES = [  # one per registered model family, at its rehearsal size
    ("seist_s_dpk", 512), ("phasenet", 512), ("eqtransformer", 6000),
    ("magnet", 512), ("baz_network", 512), ("distpt_network", 512),
    ("ditingmotion", 128), ("nemotron3_tiny", 256),
]
# baz_network's eigendecomposition has no bf16 lowering on the CPU backend;
# distpt_network is registered without a task spec (three channels).
FP32_ONLY = {"baz_network"}


def test_families_cover_the_registry():
    seist_tpu.load_all()
    from seist_tpu import registry

    def family(name):  # a family's presets share its first word
        return name.split("_")[0] if name.startswith(("seist", "nemotron3")) else name

    families = {family(name) for name in registry.MODELS.names()}
    assert families == {family(n) for n, _ in FAMILIES}


@pytest.mark.parametrize("name, in_samples", FAMILIES)
def test_model_family_ops_have_owners(name, in_samples):
    """What keeps REGIONS true when a module is renamed: of the
    instructions that carry an op_name at all, nearly every one falls into
    a region, and few of those into the catch-all unless the family has no
    region of its own. By count over everything that runs, ``unowned`` is
    what the CPU backend inserts without metadata (carry copies around the
    dropout masks' RNG loops, convert fusions): under a quarter here, 3% of
    the step's time on the chip (PERF.md section 5)."""
    seist_tpu.load_all()
    try:
        in_channels = taskspec.get_num_inchannels(name)
    except KeyError:
        in_channels = 3
    model = api.create_model(name, in_channels=in_channels, in_samples=in_samples)
    variables = api.init_variables(
        model, seed=0, in_samples=in_samples, in_channels=in_channels)
    state = create_train_state(model, variables, optax.adam(1e-3))
    step = jit_step(
        make_train_step(
            NO_TRANSFORMS,
            lambda o, t: sum(jnp.mean(a ** 2) for a in jax.tree.leaves(o)),
            compute_dtype="fp32" if name in FP32_ONLY else "bf16", guard=True),
        donate_state=False,
    )
    x = api.example_input(model, 4, in_samples, in_channels, abstract=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    text = step.jitted.lower(state, x, None, key).compile().as_text()
    m = scopes.parse_hlo(text)
    named = [v for v in m.values() if v["op_name"]]
    lost = [v["op_name"] for v in named if v["region"] == scopes.UNOWNED]
    assert len(lost) < 0.02 * len(named), sorted(set(lost))[:10]
    unowned = sum(v["region"] == scopes.UNOWNED for v in m.values())
    assert unowned < 0.25 * len(m), (unowned, len(m))
    regions = {v["region"] for v in m.values()}
    assert {"optimizer", "loss"} <= regions
    expected = {"seist_s_dpk": {"stem", "msmc", "attention", "mlp", "head",
                                "stage_aggr"},
                "phasenet": {"conv_down", "conv_up"},
                "eqtransformer": {"lstm"}, "magnet": {"lstm"},
                "nemotron3_tiny": {"ssm_proj", "ssm_scan", "moe_router",
                                   "moe_experts", "moe_shared", "embed",
                                   "lm_head", "attention"}}
    assert expected.get(name, set()) <= regions, regions
