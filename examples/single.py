"""Single-device training — the ``tfsingle.py`` equivalent (SURVEY.md §3.1).

Run: ``python examples/single.py``

Trains the 784→100→10 sigmoid/softmax MLP with SGD lr=0.001, batch 100, for
100 epochs, printing the reference's Step/Epoch/Batch/Cost/AvgTime lines and
per-epoch Test-Accuracy, and writing cost/accuracy scalars to ./logs.
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
    trainer = build_trainer(config_from_env(TrainConfig()))
    trainer.run()
