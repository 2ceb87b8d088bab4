"""Language-model training + generation — the capability the reference never
had (its one model is the MLP classifier, reference tfsingle.py:23-42).

Run: ``python examples/lm.py [epochs] [max_new]``

Drives the full LM lifecycle through :class:`~train.lm_trainer.LMTrainer`
(the reference loop contract — Step/Cost/AvgTime lines, per-epoch held-out
perplexity, scanned-epoch fast path, optional checkpointing via
``DTF_LM_CKPT=dir``) on the synthetic copy task (sequences ``x · x`` — the
model must attend back and reproduce the first half), then generates from a
held-out prompt with the static-shape KV cache: greedy and sampled.
``DTF_LM_FLASH=1`` switches the causal attention to the Pallas flash
kernel.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data import copy_corpus
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.train import LMTrainer
from distributed_tensorflow_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def main(epochs: int = 8, max_new: int = 16) -> None:
    datasets = copy_corpus(num=4096, half_len=8, vocab=61, seed=0)
    model = GPTLM(
        vocab_size=61,
        max_len=48,
        model_dim=64,
        num_heads=4,
        num_layers=2,
        compute_dtype=jnp.float32,
        attention_impl="flash" if os.environ.get("DTF_LM_FLASH") else "xla",
        flash_min_len=0,  # demo corpus is toy-length; keep the knob real
    )
    trainer = LMTrainer(
        model,
        datasets,
        TrainConfig(
            epochs=epochs,
            batch_size=64,
            optimizer="adam",
            learning_rate=3e-3,
            log_frequency=20,
            checkpoint_dir=os.environ.get("DTF_LM_CKPT"),
        ),
    )
    result = trainer.run()
    print(f"held-out perplexity: {result['perplexity']:.2f}")

    params = trainer.state.params
    rng = np.random.default_rng(1)
    half = rng.integers(0, 61, size=(2, 8))
    prompt = jnp.asarray(
        np.concatenate([half, half[:, :2]], axis=1), jnp.int32
    )  # first half + 2 copied tokens: the model should continue the copy
    greedy = model.greedy_decode(params, prompt, max_new)
    sampled = model.sample_decode(
        params, prompt, max_new, jax.random.key(0), temperature=0.7,
        top_k=8, top_p=0.95
    )
    beam = model.beam_decode(params, prompt, max_new, 4)
    ncheck = min(6, max_new)
    copied = np.asarray(greedy[:, 10 : 10 + ncheck])
    want = half[:, 2 : 2 + ncheck]
    print(f"greedy continuation:  {np.asarray(greedy)[0, 10:].tolist()}")
    print(f"sampled continuation: {np.asarray(sampled)[0, 10:].tolist()}")
    print(f"beam-4 continuation:  {np.asarray(beam)[0, 10:].tolist()}")
    print(f"copy-accuracy (greedy): {(copied == want).mean():.2f}")
    print("Done")


if __name__ == "__main__":
    configure_compile_cache()
    argv = [int(a) for a in sys.argv[1:3]]
    main(*argv)
