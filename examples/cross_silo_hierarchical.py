"""Hierarchical cross-silo: a 2-chip silo (per-step gradient psum over a
local mesh) + a silo with a DCN slave (round-level averaging)."""

# run-from-checkout shim: make the repo importable without `pip install -e .`
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..")))

import threading
import time

import fedml_tpu as fedml
from fedml_tpu import data as data_mod, models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.cross_silo import FedMLCrossSiloClient, FedMLCrossSiloServer


def mk(**kw):
    base = dict(training_type="cross_silo", dataset="synthetic", model="lr",
                client_num_in_total=2, client_num_per_round=2, comm_round=4,
                epochs=2, batch_size=8, learning_rate=0.2,
                backend="LOOPBACK", run_id="hier-demo")
    base.update(kw)
    return fedml.init(Arguments(overrides=base), should_init_logs=False)


args_s = mk(role="server")
ds, od = data_mod.load(args_s)
bundle = model_mod.create(args_s, od)
server = FedMLCrossSiloServer(args_s, None, ds, bundle)
import jax

silo1 = dict(silo_device_indices=[0, 1]) if len(jax.devices()) >= 2 else {}
clients = [
    FedMLCrossSiloClient(mk(role="client", rank=1, **silo1), None, ds, bundle),
    FedMLCrossSiloClient(mk(role="client", rank=2, silo_proc_num=2), None, ds, bundle),
]
threads = [threading.Thread(target=c.run, daemon=True) for c in clients]
for t in threads:
    t.start()
time.sleep(0.1)
result = server.run()
# each client's receive loop ends on the server's finish message: wait
# for it, so no client thread is still inside JAX at interpreter exit
for t in threads:
    t.join(timeout=60)
if any(t.is_alive() for t in threads):
    raise SystemExit("a client did not finish")
print(result)
