"""fedml_tpu — a TPU-native federated & distributed ML framework.

From-scratch JAX/XLA re-founding of the capabilities of FedML
(``/root/reference``, v0.7.285). API shape preserved from the reference's
``python/fedml/__init__.py:27-311`` and launchers (one-line ``run_simulation``,
five-line init → device → data → model → run), architecture re-designed
TPU-first: FL clients are shards of a device-mesh axis, aggregation is a
weighted on-device collective, local training is a ``lax.scan`` under ``vmap``,
and cross-silo FL is an async message plane over gRPC/TCP.
"""

from __future__ import annotations

import logging
import threading as _threading
from typing import Optional

from . import constants  # noqa: F401
from .arguments import Arguments, load_arguments
from .utils.seed import seed_everything

__version__ = "0.1.0"

_global_args: Optional[Arguments] = None
# guards the ambient-args latch (graftiso I001): concurrent inits (the
# multi-tenant shape) must not interleave the publish
_global_args_lock = _threading.Lock()


def init(args: Optional[Arguments] = None, should_init_logs: bool = True) -> Arguments:
    """Initialise the framework (reference: ``fedml.init``, __init__.py:27-109).

    Loads YAML config (``--cf``), seeds RNGs deterministically, and performs
    per-platform setup. Unlike the reference there is no MPI rank discovery or
    spawn-method fiddling — the TPU runtime discovers its mesh from JAX.
    """
    global _global_args
    if should_init_logs:
        logging.basicConfig(
            level=logging.INFO,
            format="[fedml_tpu] %(asctime)s %(levelname)s %(name)s: %(message)s",
        )
    if args is None:
        args = load_arguments()
    # before the first compile (seeding builds a PRNGKey): JAX opens the
    # persistent cache once per process
    cache_dir = device.enable_compilation_cache()
    args.rng = seed_everything(int(args.random_seed))
    _update_client_id_list(args)
    from .core import mlops

    mlops.init(args)
    with _global_args_lock:
        _global_args = args
    logging.getLogger(__name__).info(
        "init: platform=%s backend=%s optimizer=%s compile_cache=%s",
        args.training_type,
        args.backend,
        args.federated_optimizer,
        cache_dir,
    )
    return args


def _update_client_id_list(args: Arguments) -> None:
    """Synthesise client id list when absent (reference: __init__.py:259-311)."""
    cil = getattr(args, "client_id_list", None)
    if not cil or cil in ("[]", "None"):
        args.client_id_list = str(list(range(1, args.client_num_in_total + 1)))


def get_args() -> Optional[Arguments]:
    return _global_args


# ---------------------------------------------------------------------------
# One-line launchers (reference: launch_simulation.py:10-30,
# launch_cross_silo_horizontal.py:7-52, launch_cross_device.py:6-28)
# ---------------------------------------------------------------------------
def run_simulation(backend: str = constants.FEDML_SIMULATION_TYPE_SP):
    """One-line FL simulation: init → device → data → model → run.

    Returns the final eval metrics (an upgrade over the reference's
    ``launch_simulation.py``, which discards them).
    """
    from . import data as data_mod
    from . import models as model_mod
    from .runner import FedMLRunner

    args = load_arguments(
        constants.FEDML_TRAINING_PLATFORM_SIMULATION, comm_backend=backend
    )
    args = init(args)
    device = get_device(args)
    dataset, output_dim = data_mod.load(args)
    model = model_mod.create(args, output_dim)
    runner = FedMLRunner(args, device, dataset, model)
    return runner.run()


def run_cross_silo_server(**kwargs):
    from .cross_silo import run_server

    return run_server(**kwargs)


def run_cross_silo_client(**kwargs):
    from .cross_silo import run_client

    return run_client(**kwargs)


def get_device(args: Optional[Arguments] = None):
    from .device import get_device as _get

    return _get(args)


# Sub-module conveniences mirroring `fedml.device` / `fedml.data` / `fedml.model`
from . import device  # noqa: E402,F401
