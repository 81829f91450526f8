"""Seconds a step's device work is bracketed by the runtime on the host: from
the loop calling ``train_step`` to the device starting the step program
(launch: argument checks, the batch's sharded ``device_put``, dispatch), plus
from the device finishing it on the chip that ends last to the loop having
the loss (wake: the loss's transfer and waking the thread that waits in
``float(loss)``). Read as the ``step`` span's start to the ``loss_sync``
span's end (RoundRecord ``spans`` on the trace's clock,
``benchmark/host_spans.py``) less the step program's time on the device
(``XLA Modules`` line), median over the traced steps. The two parts are
reported as their sum because each holds the offset between the profiler's
device timeline and the host's clock, which measured 0.4 to 2.2 ms on a v5e
(PERF.md section 7) and cancels only in the sum. Layer: Cheetah runner
(``cheetah/runner.py``). Moves ``tokens_per_s_per_chip`` as
``cheetah_runner.data_s_per_step`` does: the loop is synchronous, so the
device idles for all of it; bounded end to end by
``wall_tokens_per_s_per_chip`` on four chips only, whose host is theirs."""

from benchmark import host_spans


def read(run):
    return host_spans.median_of(run, "launch_wake")
