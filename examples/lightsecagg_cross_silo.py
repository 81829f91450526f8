"""LightSecAgg: the server only ever sees masked models; dropout-tolerant."""

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
                federated_optimizer="LSA", client_num_in_total=3,
                client_num_per_round=3, comm_round=3, epochs=2, batch_size=16,
                learning_rate=0.1, backend="LOOPBACK", run_id="lsa-demo",
                lsa_privacy_guarantee=1, lsa_prime_bits=31)
    base.update(kw)
    return fedml.init(Arguments(overrides=base), should_init_logs=False)


args_s = mk(role="server")
ds, od = data_mod.load(args_s)
bundle = model_mod.create(args_s, od)
server = FedMLCrossSiloServer(args_s, None, ds, bundle)
clients = [FedMLCrossSiloClient(mk(role="client", rank=r), None, ds, bundle)
           for r in (1, 2, 3)]
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
