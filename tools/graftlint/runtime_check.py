"""Runtime-assisted purity check: trace round functions under
``jax.make_jaxpr`` and inspect the result.

The static rules reason about syntax; this closes the loop on the real
artifact. A round function is accepted when

- tracing succeeds with abstract inputs (no data-dependent Python control
  flow / host sync that throws under trace),
- the closed jaxpr carries **no effects** (no ``debug_callback`` /
  ``io_callback`` / ``pure_callback`` equations anywhere, recursively),
- tracing produced **no stdout/stderr output** (a ``print`` that fires at
  trace time is a silent lie — it will never run again), and
- tracing twice yields the **same jaxpr** (a mismatch means global mutable
  state — RNG advances, counters — leaked into the trace).

``check_round_engine`` builds tiny FedAvg/FedOpt/SCAFFOLD configs the same
way the parity tests do and verifies ``round_engine.build_round_core``'s
program for each, so ``python -m tools.graftlint --runtime`` certifies the
actual round function, not a model of it.
"""

from __future__ import annotations

import contextlib
import io
import os
import sys
from typing import Any, Callable, List, Sequence

from .findings import Finding


def trace_purity_issues(fn: Callable, example_args: Sequence[Any],
                        name: str = "fn") -> List[str]:
    """Trace ``fn`` twice under ``jax.make_jaxpr``; return issue strings."""
    import jax

    issues: List[str] = []
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf), contextlib.redirect_stderr(buf):
            # fresh wrapper objects per trace: jax caches the jaxpr on
            # function identity, which would hide nondeterministic traces
            closed1 = jax.make_jaxpr(lambda *a: fn(*a))(*example_args)
            closed2 = jax.make_jaxpr(lambda *a: fn(*a))(*example_args)
    except Exception as e:  # noqa: BLE001 - any trace failure is the finding
        return [f"{name}: tracing failed under jax.make_jaxpr: "
                f"{type(e).__name__}: {e}"]
    out = buf.getvalue()
    if out.strip():
        issues.append(
            f"{name}: tracing wrote to stdout/stderr ({out.strip()[:120]!r})"
            " — host I/O fires at trace time only"
        )
    effects = getattr(closed1, "effects", None)
    if effects:
        issues.append(f"{name}: jaxpr carries effects {sorted(map(str, effects))}")
    for prim in _callback_prims(closed1.jaxpr):
        issues.append(f"{name}: jaxpr contains host-callback primitive "
                      f"`{prim}`")
    consts_differ = len(closed1.consts) != len(closed2.consts) or any(
        not _consts_equal(a, b)
        for a, b in zip(closed1.consts, closed2.consts)
    )
    if str(closed1) != str(closed2) or consts_differ:
        issues.append(
            f"{name}: two traces produced different jaxprs — global mutable "
            "state (np.random, counters) leaked into the trace"
        )
    return issues


def _consts_equal(a: Any, b: Any) -> bool:
    try:
        import numpy as np

        return bool(np.array_equal(np.asarray(a), np.asarray(b)))
    except Exception:  # noqa: BLE001 - non-array consts: fall back
        return a is b or a == b


def _callback_prims(jaxpr) -> List[str]:
    found: List[str] = []

    def walk(jp) -> None:
        for eqn in jp.eqns:
            pname = str(eqn.primitive)
            if "callback" in pname:
                found.append(pname)
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", None)
                if inner is not None:
                    walk(inner)
                if isinstance(v, (list, tuple)):
                    for item in v:
                        inner = getattr(item, "jaxpr", None)
                        if inner is not None:
                            walk(inner)

    walk(jaxpr)
    return found


# ---------------------------------------------------------------------------
# Round-engine certification
# ---------------------------------------------------------------------------

_CONFIGS = (
    dict(federated_optimizer="FedAvg"),
    dict(federated_optimizer="FedOpt", server_optimizer="adam",
         server_lr=0.03),
    dict(federated_optimizer="SCAFFOLD"),
)


def _tiny_api(overrides: dict):
    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.simulation.sp_api import FedAvgAPI

    base = dict(
        dataset="synthetic", model="lr", client_num_in_total=8,
        client_num_per_round=4, comm_round=1, epochs=1, batch_size=8,
        learning_rate=0.1,
    )
    base.update(overrides)
    args = fedml.init(Arguments(overrides=base), should_init_logs=False)
    ds, od = data_mod.load(args)
    return FedAvgAPI(args, fedml.get_device(args), ds,
                     model_mod.create(args, od))


def check_round_engine(repo_root: str) -> List[Finding]:
    """Trace ``build_round_core`` for the tiny reference configs."""
    sys.path.insert(0, repo_root)
    try:
        from fedml_tpu.simulation.round_engine import build_round_core
    except Exception as e:  # pragma: no cover - env without the package
        # environment problem, not a lint finding — the CLI maps this to
        # exit code 2 so CI distinguishes "tool unavailable" from "impure"
        raise RuntimeError(
            f"graftlint --runtime unavailable: {type(e).__name__}: {e}"
        ) from e

    findings: List[Finding] = []
    rel = os.path.join("fedml_tpu", "simulation",
                       "round_engine.py").replace(os.sep, "/")
    for overrides in _CONFIGS:
        opt = overrides["federated_optimizer"]
        api = _tiny_api(overrides)
        per = api._cohort_size()
        core = build_round_core(api, n_cohort=per, n_valid=per)
        issues = trace_purity_issues(
            core, api._round_inputs(0), name=f"build_round_core[{opt}]",
        )
        findings += [
            # line_text carries the issue so each distinct runtime failure
            # gets its own baseline key (path::rule::line_text) instead of
            # all of them collapsing onto one suppressible entry
            Finding(rule="G004", path=rel, line=1, col=0,
                    message=f"runtime purity check: {msg}",
                    line_text=f"runtime::{msg}")
            for msg in issues
        ]
    return findings
