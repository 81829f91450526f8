"""Cross-silo FL over gRPC with server + clients as SEPARATE OS processes.

The deployment shape the reference's ``grpc_fedavg_mnist_lr_example`` runs
(one process per organization, DCN between them), on this framework's
single gRPC backend — with the r5 direct-tensor wire format on
(``grpc_wire_format: raw``): zero-copy tensor frames, chunked streaming
for bulk payloads (``core/distributed/tensor_transport.py``).

The script re-execs itself for the client roles, so one file is the whole
multi-process world:  python cross_silo_grpc_multiprocess.py
"""

# run-from-checkout shim: make the repo importable without `pip install -e .`
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..")))

import os
import socket
import subprocess
import sys

import fedml_tpu as fedml
from fedml_tpu import data as data_mod, models as model_mod
from fedml_tpu.arguments import Arguments

N_CLIENTS = 2


def mk(role, rank, port):
    return fedml.init(Arguments(overrides=dict(
        training_type="cross_silo", dataset="synthetic", model="lr",
        client_num_in_total=N_CLIENTS, client_num_per_round=N_CLIENTS,
        comm_round=3, epochs=2, batch_size=8, learning_rate=0.2,
        backend="GRPC", comm_port=port, comm_host="127.0.0.1",
        grpc_wire_format="raw",  # direct-tensor frames + streaming
        role=role, rank=rank, run_id="grpc-mp-demo",
    )), should_init_logs=False)


def main() -> None:
    if "--client" in sys.argv:
        rank = int(sys.argv[sys.argv.index("--client") + 1])
        port = int(sys.argv[sys.argv.index("--port") + 1])
        from fedml_tpu.cross_silo import FedMLCrossSiloClient

        args = mk("client", rank, port)
        ds, od = data_mod.load(args)
        FedMLCrossSiloClient(args, None, ds, model_mod.create(args, od)).run()
        return

    # parent = the server org; pick a free base port for the world
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    from fedml_tpu.cross_silo import FedMLCrossSiloServer

    args = mk("server", 0, port)
    ds, od = data_mod.load(args)
    server = FedMLCrossSiloServer(args, None, ds, model_mod.create(args, od))
    # client organisations run on the CPU: this parent holds the chip (if
    # any), and a chip belongs to one process
    procs = [
        subprocess.Popen([sys.executable, __file__, "--client", str(r),
                          "--port", str(port)],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
        for r in range(1, N_CLIENTS + 1)
    ]
    ok = False
    try:
        result = server.run()
        print("grpc multiprocess result:", result)
        assert result is not None and result["test_acc"] > 0.5
        ok = True
    finally:
        for p in procs:
            if not ok:
                p.kill()  # don't orphan clients (or mask the real error
                #           with TimeoutExpired) when the server failed
            p.wait(timeout=60)
    print("cross-silo gRPC multi-process ok")


if __name__ == "__main__":
    main()
