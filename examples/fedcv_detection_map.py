"""Federated object detection with mAP@0.5 (FedCV detection family).

reference: ``python/app/fedcv/object_detection`` — YOLOv5 federated
fine-tuning with mAP eval. Here: the dense anchor-free CenterNet head
trains through the sp engine, and evaluation is true detection decoding
(3x3 peak NMS + top-k) scored with VOC-style mAP@0.5/@0.25
(``ml/detection_metrics.py``) — not just per-center class accuracy. Staging
a COCO-format dataset (annotations json + images dir) under
``data_cache_dir`` swaps the synthetic rectangles for real images via
``data/real_readers.try_load_coco_detection``.
"""

# run-from-checkout shim: make the repo importable without `pip install -e .`
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.abspath(_os.path.join(_os.path.dirname(__file__), "..")))

import fedml_tpu as fedml
from fedml_tpu import data as data_mod, models as model_mod
from fedml_tpu.arguments import Arguments
from fedml_tpu.ml.detection_metrics import (
    collect_detection_logits, map_at_50,
)
from fedml_tpu.simulation.sp_api import FedAvgAPI

args = fedml.init(Arguments(overrides=dict(
    dataset="coco128_det", model="centernet", client_num_in_total=4,
    client_num_per_round=4, comm_round=6, epochs=2, batch_size=8,
    learning_rate=3e-3, client_optimizer="adam", frequency_of_the_test=100,
)), should_init_logs=False)
ds, od = data_mod.load(args)
bundle = model_mod.create(args, od)
api = FedAvgAPI(args, fedml.get_device(args), ds, bundle)

for r in range(int(args.comm_round)):
    args.round_idx = r
    api.run_round(r)

# ONE forward over the test set; score the same logits at both IoUs
import numpy as np

logits = collect_detection_logits(bundle, api.global_params, ds.test_x)
targets = [np.asarray(t, np.float32) for t in ds.test_y]
m50 = map_at_50(logits, targets)
m25 = map_at_50(logits, targets, iou_thresh=0.25)
print(f"federated detection: mAP@0.5={m50['map50']:.3f} "
      f"mAP@0.25={m25['map50']:.3f} over {m50['total_gt']:.0f} GT boxes")
assert m25["map50"] > 0.05, "no localization signal"
print("fedcv detection mAP example ok")
