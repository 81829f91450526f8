"""Framework-wide constants.

Mirrors the role of the reference's ``python/fedml/constants.py:1-44`` (platform
names, backend names, federated-optimizer registry), re-grounded for a TPU-native
stack: the simulation backends are single-process ("sp") and a TPU device-mesh
backend ("mesh") that replaces the reference's MPI/NCCL process-per-worker model.
"""

# ---------------------------------------------------------------------------
# Training platforms (reference: constants.py:2-5)
# ---------------------------------------------------------------------------
FEDML_TRAINING_PLATFORM_SIMULATION = "simulation"
FEDML_TRAINING_PLATFORM_CROSS_SILO = "cross_silo"
FEDML_TRAINING_PLATFORM_CROSS_DEVICE = "cross_device"
FEDML_TRAINING_PLATFORM_DISTRIBUTED = "distributed"  # "Cheetah" — real here, stub in ref

# ---------------------------------------------------------------------------
# Simulation backends (reference: constants.py:7-9 — sp / MPI / NCCL).
# TPU-native: "sp" keeps the single-process semantics; "mesh" maps simulated FL
# clients onto a jax.sharding.Mesh axis (replaces both MPI and NCCL backends).
# ---------------------------------------------------------------------------
FEDML_SIMULATION_TYPE_SP = "sp"
FEDML_SIMULATION_TYPE_MESH = "mesh"
SIMULATION_BACKENDS = (FEDML_SIMULATION_TYPE_SP, FEDML_SIMULATION_TYPE_MESH)

# Cross-silo / cross-device transports (reference: fedml_comm_manager.py:72-133).
COMM_BACKEND_LOOPBACK = "LOOPBACK"  # in-process test fixture (absent in reference)
COMM_BACKEND_GRPC = "GRPC"
COMM_BACKEND_TCP = "TCP"
COMM_BACKEND_MQTT = "MQTT"  # broker plane (control only; payload store = S3 split)
COMM_BACKENDS = (
    COMM_BACKEND_LOOPBACK, COMM_BACKEND_GRPC, COMM_BACKEND_TCP,
    COMM_BACKEND_MQTT,
)

# Cross-silo scenarios (reference: constants.py:26-28)
FEDML_CROSS_SILO_SCENARIO_HORIZONTAL = "horizontal"
FEDML_CROSS_SILO_SCENARIO_HIERARCHICAL = "hierarchical"

# ---------------------------------------------------------------------------
# Federated optimizers (reference: constants.py:29-44 declares 16 names)
# ---------------------------------------------------------------------------
FEDML_FEDERATED_OPTIMIZER_FEDAVG = "FedAvg"
FEDML_FEDERATED_OPTIMIZER_FEDAVG_SEQ = "FedAvg_seq"
FEDML_FEDERATED_OPTIMIZER_FEDOPT = "FedOpt"
FEDML_FEDERATED_OPTIMIZER_FEDPROX = "FedProx"
FEDML_FEDERATED_OPTIMIZER_FEDNOVA = "FedNova"
FEDML_FEDERATED_OPTIMIZER_FEDSGD = "FedSGD"
FEDML_FEDERATED_OPTIMIZER_FEDDYN = "FedDyn"
FEDML_FEDERATED_OPTIMIZER_SCAFFOLD = "SCAFFOLD"
FEDML_FEDERATED_OPTIMIZER_MIME = "Mime"
FEDML_FEDERATED_OPTIMIZER_FEDGAN = "FedGAN"
FEDML_FEDERATED_OPTIMIZER_FEDGKT = "FedGKT"
FEDML_FEDERATED_OPTIMIZER_FEDNAS = "FedNAS"
FEDML_FEDERATED_OPTIMIZER_FEDSEG = "FedSeg"
FEDML_FEDERATED_OPTIMIZER_SPLIT_NN = "SplitNN"
FEDML_FEDERATED_OPTIMIZER_VFL = "vertical_fl"
FEDML_FEDERATED_OPTIMIZER_DECENTRALIZED_FL = "decentralized_fl"
FEDML_FEDERATED_OPTIMIZER_HIERARCHICAL_FL = "hierarchical_fl"
FEDML_FEDERATED_OPTIMIZER_TURBOAGGREGATE = "turboaggregate"
FEDML_FEDERATED_OPTIMIZER_LSA = "LSA"  # LightSecAgg

# ---------------------------------------------------------------------------
# Mesh axis names used throughout the framework
# ---------------------------------------------------------------------------
MESH_AXIS_CLIENTS = "clients"   # FL simulation: one shard = a slice of clients
MESH_AXIS_DATA = "data"         # Cheetah: data parallel
MESH_AXIS_FSDP = "fsdp"         # Cheetah: fully-sharded data parallel
MESH_AXIS_TENSOR = "tensor"     # Cheetah: tensor parallel (MXU-aligned sharding)
MESH_AXIS_SEQUENCE = "sequence" # Cheetah: sequence/context parallel (ring attention)
MESH_AXIS_EXPERT = "expert"     # Cheetah: expert parallel (MoE)
MESH_AXIS_PIPELINE = "pipeline" # Cheetah: pipeline parallel
