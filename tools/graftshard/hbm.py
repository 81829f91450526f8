"""S005 — static HBM-budget estimation, no hardware required.

Walks a model config × partition rules × optimizer state through
``jax.eval_shape`` and prices every leaf against an *abstract* mesh (a dict
of axis extents — no devices are touched, so a CPU-only host can budget a
v5p pod):

- params come out of ``jax.eval_shape(model.init)`` still wearing their
  ``nn.Partitioned`` logical axis names; ``logical_to_mesh_spec`` maps them
  to mesh axes exactly as the real trainer does;
- optimizer state is shaped by ``jax.eval_shape(opt.init)`` and sharded by
  the longest-path-suffix match the pipeline uses (``_opt_state_specs``) —
  adam's ``count`` scalar stays replicated, the moments follow their param;
- gradients mirror params (transient but resident at peak);
- the batch (tokens+mask) shards over the data-parallel extent.

Per-device bytes = leaf bytes ÷ ∏(extents of the axes its spec names),
with an S002 finding when a sharded dimension is not divisible by its axis
extents (XLA pads the shard; the budget then lies per-device). Totals are
compared against the chip HBM table — exceeding a requested chip's budget
is an S005 finding; the full report rides the ``--json`` payload either
way. Activations/workspace are deliberately NOT estimated: they belong to
the compiler (a compile for a described TPU reports them in its
``memory_analysis()``); S005 bounds the *state* floor, which
is what the partition rules control.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

from .findings import Finding

GiB = 1024 ** 3

# chip -> HBM bytes (None = host memory, report-only)
CHIP_HBM: Dict[str, Optional[int]] = {
    "v5e": 16 * GiB,
    "v5p": 95 * GiB,
    "cpu": None,
}

# leave headroom for XLA workspace/fragmentation
HBM_FILL_FRACTION = 0.95

_SHARDING_REL = "fedml_tpu/parallel/sharding.py"
_TRAIN_STEP_REL = "fedml_tpu/parallel/train_step.py"


def model_registry() -> Dict[str, object]:
    """--model name -> TransformerConfig factory (lazy: imports jax)."""
    from fedml_tpu.parallel.transformer import TransformerConfig

    return {
        "7b": TransformerConfig.llama2_7b,
        "llama2_7b": TransformerConfig.llama2_7b,
        "tiny": TransformerConfig.tiny,
    }


def parse_mesh_arg(text: str) -> List[Tuple[Optional[str], str, Dict[str, int]]]:
    """``--mesh`` → ``[(chip|None, label, axis extents)]``.

    Comma- or ``;``-separated entries; each is ``[chip:]shape`` where shape
    is either a topology product (``4x4`` → 16 chips, all on ``fsdp``) or
    explicit ``+``-joined axes
    (``fsdp=8+tensor=2``). A chipless entry is priced against every chip
    in the table.
    """
    rows: List[Tuple[Optional[str], str, Dict[str, int]]] = []
    for raw in (text or "").replace(";", ",").split(","):
        raw = raw.strip()
        if not raw:
            continue
        chip: Optional[str] = None
        shape = raw
        if ":" in raw:
            chip, _, shape = raw.partition(":")
            chip = chip.strip().lower()
            if chip not in CHIP_HBM:
                raise ValueError(
                    f"unknown chip {chip!r} in --mesh entry {raw!r} "
                    f"(known: {', '.join(sorted(CHIP_HBM))})")
        if "=" in shape:
            axes: Dict[str, int] = {}
            for part in shape.split("+"):
                name, _, n = part.partition("=")
                axes[name.strip()] = int(n)
        else:
            n = math.prod(int(d) for d in shape.lower().split("x"))
            axes = {"fsdp": n}
        rows.append((chip, shape.strip(), axes))
    if not rows:
        raise ValueError("--mesh given but empty")
    return rows


def _per_device_elems(spec, shape, axes: Dict[str, int],
                      leaf_name: str) -> Tuple[int, List[str]]:
    """(per-device element count, divisibility problems) for one leaf.

    An indivisible dimension is priced at its PADDED shard size
    (ceil(size/extent) — what XLA actually allocates per device), and
    reported as an S002 problem."""
    dims = tuple(spec)
    elems = 1
    problems: List[str] = []
    for dim_idx, size in enumerate(shape):
        dim = dims[dim_idx] if dim_idx < len(dims) else None
        extent = 1
        for ax in (dim if isinstance(dim, tuple) else (dim,)):
            if ax is not None:
                extent *= int(axes.get(ax, 1))
        if extent > 1 and size % extent:
            problems.append(
                f"{leaf_name}: dim {dim_idx} (size {size}) not divisible "
                f"by axis extent {extent} ({dim})")
        elems *= -(-int(size) // extent)  # ceil: the padded shard
    return elems, problems


def estimate_budget(model_name: str, mesh_text: str, *,
                    seq_len: int = 0, batch_per_device: int = 1,
                    mu_dtype: str = "bfloat16") -> Tuple[List[Finding], Dict]:
    """→ (findings, report dict for the ``--json`` payload)."""
    registry = model_registry()
    if model_name not in registry:
        raise ValueError(
            f"unknown --model {model_name!r} "
            f"(known: {', '.join(sorted(registry))})")

    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from fedml_tpu.parallel.pipeline import _opt_state_specs
    from fedml_tpu.parallel.sharding import logical_to_mesh_spec
    from fedml_tpu.parallel.train_step import make_optimizer
    from fedml_tpu.parallel.transformer import Transformer

    cfg = registry[model_name]()
    seq_len = int(seq_len) or int(cfg.max_seq_len)
    model = Transformer(cfg)
    dummy = jnp.zeros((1, 8), jnp.int32)
    boxed = jax.eval_shape(
        lambda r: model.init(r, dummy), jax.random.PRNGKey(0)
    )["params"]

    is_boxed = lambda x: isinstance(x, nn.Partitioned)  # noqa: E731
    leaves = []  # (name, spec, ShapeDtypeStruct)
    from jax.tree_util import tree_flatten_with_path

    flat, _ = tree_flatten_with_path(boxed, is_leaf=is_boxed)
    for path, p in flat:
        name = "/".join(_key_str(k) for k in path)
        if is_boxed(p):
            spec = logical_to_mesh_spec(p.names)
            val = p.value
        else:
            from jax.sharding import PartitionSpec as P

            spec, val = P(), p
        leaves.append((name, spec, val))

    unboxed = jax.tree.map(lambda p: p.value if is_boxed(p) else p, boxed,
                           is_leaf=is_boxed)
    opt = make_optimizer(mu_dtype=jnp.dtype(mu_dtype))
    opt_abs = jax.eval_shape(opt.init, unboxed)
    spec_by_name = {name: spec for name, spec, _v in leaves}
    p_spec = _named_spec_tree(unboxed, spec_by_name)
    o_spec = _opt_state_specs(p_spec, opt_abs)
    opt_leaves = _zip_spec_leaves(opt_abs, o_spec)

    n_params = sum(int(math.prod(v.shape)) for _n, _s, v in leaves)

    findings: List[Finding] = []
    rows = []
    for chip, label, axes in parse_mesh_arg(mesh_text):
        n_dev = math.prod(axes.values())
        div_problems: List[str] = []

        def total(entries):
            import jax.numpy as jnp

            tot = 0
            for name, spec, val in entries:
                elems, problems = _per_device_elems(spec, val.shape, axes,
                                                    name)
                div_problems.extend(problems)
                tot += elems * jnp.dtype(val.dtype).itemsize
            return tot

        params_b = total(leaves)
        grads_b = params_b  # value_and_grad mirrors the param tree
        opt_b = total(opt_leaves)
        dp = int(axes.get("data", 1)) * int(axes.get("fsdp", 1))
        batch_b = int(batch_per_device) * seq_len * 4 * 2  # tokens+mask i32
        total_b = params_b + grads_b + opt_b + batch_b

        for problem in sorted(set(div_problems)):
            findings.append(Finding(
                rule="S002", path=_SHARDING_REL, line=1, col=0,
                message=f"[{label}] {problem} — XLA pads the shard "
                        "per-device; the budget (and the step) pay for "
                        "the padded size",
                line_text=f"hbm-divisibility::{model_name}::{label}::"
                          f"{problem}"))

        for chip_name in ([chip] if chip else sorted(CHIP_HBM)):
            budget = CHIP_HBM[chip_name]
            fits = (budget is None
                    or total_b <= budget * HBM_FILL_FRACTION)
            rows.append({
                "model": model_name, "chip": chip_name, "mesh": label,
                "devices": n_dev, "axes": dict(axes),
                "params": n_params,
                "params_gib": round(params_b / GiB, 3),
                "grads_gib": round(grads_b / GiB, 3),
                "opt_gib": round(opt_b / GiB, 3),
                "batch_gib": round(batch_b / GiB, 6),
                "total_gib_per_device": round(total_b / GiB, 3),
                "hbm_gib": (round(budget / GiB, 1)
                            if budget is not None else None),
                "batch_global": int(batch_per_device) * dp,
                "fits": fits,
            })
            if not fits:
                findings.append(Finding(
                    rule="S005", path=_TRAIN_STEP_REL, line=1, col=0,
                    message=f"{model_name} on {chip_name}:{label} "
                            f"({n_dev} dev): resident state "
                            f"{total_b / GiB:.2f} GiB/device exceeds "
                            f"{HBM_FILL_FRACTION:.0%} of the chip's "
                            f"{budget / GiB:.0f} GiB HBM before any "
                            "activation is allocated",
                    line_text=f"hbm::{model_name}::{chip_name}::{label}"))

    report = {
        "model": model_name, "seq_len": seq_len,
        "batch_per_device": int(batch_per_device), "mu_dtype": mu_dtype,
        "headroom": HBM_FILL_FRACTION,
        "accounting": "params + grads + optimizer + batch (resident "
                      "state; compiler temps are the compile's own "
                      "memory_analysis())",
        "rows": rows,
    }
    return findings, report


def _key_str(k) -> str:
    # the one pytree-key stringifier the repo already ships — leaf names
    # here MUST match partition-rule leaf names or specs silently miss
    from fedml_tpu.scale.partition_rules import _key_name

    return _key_name(k)


def _named_spec_tree(unboxed, spec_by_name):
    """Rebuild the per-leaf spec pytree matching ``unboxed``'s structure."""
    from jax.tree_util import tree_flatten_with_path, tree_unflatten

    flat, treedef = tree_flatten_with_path(unboxed)
    out = []
    for path, _leaf in flat:
        name = "/".join(_key_str(k) for k in path)
        out.append(spec_by_name[name])
    return tree_unflatten(treedef, out)


def _zip_spec_leaves(opt_abs, o_spec):
    import jax
    from jax.sharding import PartitionSpec as P
    from jax.tree_util import tree_flatten_with_path

    flat_v, _ = tree_flatten_with_path(opt_abs)
    flat_s = jax.tree.leaves(o_spec, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_v) == len(flat_s), (len(flat_v), len(flat_s))
    out = []
    for (path, val), spec in zip(flat_v, flat_s):
        name = "opt/" + "/".join(_key_str(k) for k in path)
        out.append((name, spec, val))
    return out


def render_report(report: Dict) -> str:
    lines = [
        f"HBM budget — model {report['model']} (seq {report['seq_len']}, "
        f"batch/device {report['batch_per_device']}, "
        f"mu_dtype {report['mu_dtype']})",
        f"  accounting: {report['accounting']}",
        f"  {'chip':<5} {'mesh':<14} {'dev':>4} {'params':>8} "
        f"{'grads':>8} {'opt':>8} {'total/dev':>10} {'HBM':>7}  fit",
    ]
    for r in report["rows"]:
        hbm = f"{r['hbm_gib']:.0f}G" if r["hbm_gib"] else "host"
        lines.append(
            f"  {r['chip']:<5} {r['mesh']:<14} {r['devices']:>4} "
            f"{r['params_gib']:>7.2f}G {r['grads_gib']:>7.2f}G "
            f"{r['opt_gib']:>7.2f}G {r['total_gib_per_device']:>9.2f}G "
            f"{hbm:>7}  {'OK' if r['fits'] else 'OVER'}")
    return "\n".join(lines)
