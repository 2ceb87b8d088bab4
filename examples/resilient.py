"""Resilient single-device training: durable checkpoints + preemption +
anomaly rollback (train/resilience.py; contracts in docs/resilience.md).

Run: ``python examples/resilient.py``            # train with the full guard
     kill -TERM <pid>                            # graceful stop + final save
     python examples/resilient.py                # resumes from the newest
                                                 # VALID step_N (corrupt or
                                                 # partial saves are skipped)

Every epoch saves ``step_N`` plus a CRC32C manifest sidecar; retention
keeps the newest 3. A NaN/inf or spike epoch (cost > 3x the trailing-
window median) restores the last good checkpoint and retries on the next
data window, up to 2 times, printing a ``Rollback:`` line per event. No
reference analog: the TF1 suite configured no saver at all (SURVEY.md §5).
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.launch import build_trainer, config_from_env
from distributed_tensorflow_tpu.utils.compile_cache import (
    configure_compile_cache,
)

if __name__ == "__main__":
    configure_compile_cache()
    config = TrainConfig(
        checkpoint_dir="./checkpoints_resilient",
        keep_last_n=3,          # GC old steps; the last valid one survives
        max_rollbacks=2,        # anomaly guard budget (0 disables)
        spike_threshold=3.0,    # x trailing-window median; NaN always trips
        handle_preemption=True, # SIGTERM/SIGINT -> save at boundary, exit 0
    )
    trainer = build_trainer(config_from_env(config))
    print(f"resuming from step {trainer.start_step}" if trainer.start_step
          else "fresh start")
    trainer.run()
