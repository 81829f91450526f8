"""One-command reproduction of the reference's published accuracy table.

Each row maps a line of the reference's benchmark doc
(``doc/en/simulation/benchmark/BENCHMARK_simulation.md``; hyper-parameters
from its config blocks at lines 16-175) to a run of OUR sp engine with the
same federated config. Staged real data (the same on-disk formats the
reference consumes — ``data/real_readers.py`` + the IDX/pickle readers in
``data/datasets.py``) is picked up automatically from ``--cache-dir``;
without it the run falls back to the synthetic generators and the output
says so — a synthetic run exercises the config, it does NOT reproduce the
published number (this pod has no egress to download the corpora).

Usage:
  python tools/reproduce_baselines.py --list
  python tools/reproduce_baselines.py --row mnist_lr --cache-dir ~/fedml_data
  python tools/reproduce_baselines.py --row stackoverflow_lr \
      --cache-dir tests/fixtures/stackoverflow --rounds 4   # fixture smoke
Off-chip, run it under ``JAX_PLATFORMS=cpu``.

Prints one JSON line per run:
  {"row", "dataset", "model", "published_acc", "test_acc", "rounds",
   "data": "real"|"synthetic", "reproduces": bool|null}
``reproduces`` compares against the published number minus ``--slack``
(default 2 acc points) and is null for synthetic data.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# BENCHMARK_simulation.md table (lines 3-12) + config blocks (lines 16-175).
# Fields follow the yaml blocks verbatim; published = the "Exp" column.
ROWS = {
    "mnist_lr": dict(
        dataset="mnist", model="lr", published=81.9,
        client_num_in_total=1000, client_num_per_round=10, comm_round=200,
        epochs=1, batch_size=10, learning_rate=0.03, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:5 (config :16-34)",
    ),
    "femnist_cnn": dict(
        dataset="femnist", model="cnn", published=80.2,
        client_num_in_total=10, client_num_per_round=10, comm_round=1000,
        epochs=1, batch_size=20, learning_rate=0.03, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:6 (config :95-115)",
    ),
    "fed_cifar100_resnet18gn": dict(
        dataset="fed_cifar100", model="resnet18_gn", published=34.0,
        client_num_in_total=10, client_num_per_round=10, comm_round=4000,
        epochs=1, batch_size=10, learning_rate=0.1, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:7 (config :119-139)",
    ),
    "shakespeare_rnn": dict(
        dataset="shakespeare", model="rnn", published=53.1,
        client_num_in_total=10, client_num_per_round=10, comm_round=10,
        epochs=1, batch_size=10, learning_rate=0.8, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:8 (config :40-60)",
    ),
    "fed_shakespeare_rnn": dict(
        dataset="fed_shakespeare", model="rnn", published=57.1,
        client_num_in_total=10, client_num_per_round=10, comm_round=1000,
        epochs=1, batch_size=10, learning_rate=0.8, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:9 (config :66-87)",
    ),
    "stackoverflow_lr": dict(
        dataset="stackoverflow_lr", model="lr", published=None,
        client_num_in_total=10, client_num_per_round=10, comm_round=2000,
        epochs=1, batch_size=10, learning_rate=0.03, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:143-163 (no Exp number in table)",
    ),
    "stackoverflow_nwp_rnn": dict(
        dataset="stackoverflow_nwp", model="rnn", published=18.3,
        client_num_in_total=10, client_num_per_round=10, comm_round=2000,
        epochs=1, batch_size=10, learning_rate=0.03, client_optimizer="sgd",
        source="BENCHMARK_simulation.md:10 (config :167-188)",
    ),
}


def run_row(name: str, cache_dir: str, rounds: int | None,
            slack: float) -> dict:
    row = ROWS[name]
    import fedml_tpu as fedml
    from fedml_tpu import data as data_mod
    from fedml_tpu import models as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.runner import FedMLRunner

    overrides = dict(
        dataset=row["dataset"], model=row["model"],
        partition_method="hetero", partition_alpha=0.5,
        federated_optimizer="FedAvg",
        client_num_in_total=row["client_num_in_total"],
        client_num_per_round=row["client_num_per_round"],
        comm_round=rounds if rounds is not None else row["comm_round"],
        epochs=row["epochs"], batch_size=row["batch_size"],
        learning_rate=row["learning_rate"],
        client_optimizer=row["client_optimizer"],
        frequency_of_the_test=10_000, backend="sp",
    )
    if cache_dir:
        overrides["data_cache_dir"] = cache_dir
    args = fedml.init(Arguments(overrides=overrides), should_init_logs=False)
    ds, output_dim = data_mod.load(args)
    # natural partitions define the client count; a fixture-scale corpus
    # may hold fewer clients than the published cohort
    if int(args.client_num_per_round) > ds.client_num:
        args.client_num_per_round = ds.client_num
        args.client_num_in_total = ds.client_num
    # real on-disk data: natural LEAF/TFF partitions or the IDX/pickle
    # readers; anything else is the synthetic fallback
    real_tag = ds.meta.get("real_files")
    real = bool(ds.meta.get("natural_partition") or real_tag)
    # a string tag = real data under a DEVIATING protocol (e.g. the
    # mnist t10k-split when train images can't be staged) — reported, and
    # excluded from an unqualified "reproduces" claim below
    protocol = real_tag if isinstance(real_tag, str) else "published"
    # fixture-scale corpora can carry smaller vocab/tag spaces than the
    # registry's full-staging dims — size the model from the DATA (at full
    # staging these match the registry exactly)
    if ds.task == "tagpred":
        output_dim = int(ds.train_y.shape[-1])
    bundle = model_mod.create(args, output_dim)
    bundle.input_shape = tuple(ds.train_x.shape[2:])
    res = FedMLRunner(args, fedml.get_device(args), ds, bundle).run()
    acc = 100.0 * float(res["test_acc"])
    published = row["published"]
    out = {
        "row": name,
        "dataset": row["dataset"],
        "model": row["model"],
        "published_acc": published,
        "test_acc": round(acc, 2),
        "rounds": overrides["comm_round"],
        "data": "real" if real else "synthetic",
        "protocol": protocol,
        # an unqualified claim needs real data, the full round budget, AND
        # the published protocol; protocol deviations report the accuracy
        # comparison under "reproduces_deviating_protocol" instead
        "reproduces": (
            acc >= published - slack
            if real and published is not None and protocol == "published"
            and overrides["comm_round"] >= row["comm_round"] else None
        ),
        "source": row["source"],
    }
    if real and published is not None and protocol != "published" \
            and overrides["comm_round"] >= row["comm_round"]:
        out["reproduces_deviating_protocol"] = bool(acc >= published - slack)
    print(json.dumps(out))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--row", choices=sorted(ROWS), action="append")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--cache-dir", default="")
    ap.add_argument("--rounds", type=int, default=None,
                    help="override comm_round (smoke runs)")
    ap.add_argument("--slack", type=float, default=2.0)
    a = ap.parse_args()
    if a.list:
        for name, row in ROWS.items():
            print(f"{name:28s} {row['dataset']:18s} {row['model']:12s} "
                  f"published={row['published']}  ({row['source']})")
        return
    names = sorted(ROWS) if a.all else (a.row or [])
    if not names:
        ap.error("pass --row NAME (repeatable), --all, or --list")
    results = [run_row(n, a.cache_dir, a.rounds, a.slack) for n in names]
    bad = [r for r in results if r["reproduces"] is False]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
