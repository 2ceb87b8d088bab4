"""Serve a trained LM checkpoint: text in → text out.

The missing half of examples/text_lm.py — that script trains and decodes
in-process; this one closes the production loop the reference never had
(its only inference was the in-loop eval fetch, reference tfsingle.py:94):

1. train a few epochs with a BPE vocab, checkpointing (the trainer ships
   ``tokenizer.json`` into ``checkpoint_dir``);
2. load the checkpoint into a :class:`~distributed_tensorflow_tpu.serve.
   TextServer` — compiled bucketed prefill + chunked decode with
   continuous batching across 4 request slots;
3. serve a mixed batch of prompts (greedy and seeded nucleus sampling)
   and print the generations.

Run: ``python examples/serve_text.py [epochs] [max_new]``
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax.numpy as jnp

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data import (
    BPETokenizer,
    synthetic_documents,
    text_corpus,
)
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.serve import GenerationConfig, TextServer
from distributed_tensorflow_tpu.train import LMTrainer
from distributed_tensorflow_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def main(epochs: int = 4, max_new: int = 32) -> None:
    tok = BPETokenizer.train(synthetic_documents(512, seed=0), num_merges=64)
    datasets = text_corpus(
        num_docs=512, seq_len=64, n_val=16, n_test=16, seed=0, tokenizer=tok
    )
    model = GPTLM(
        vocab_size=tok.vocab_size,
        max_len=64 + max_new,
        model_dim=64,
        num_heads=4,
        num_layers=2,
        compute_dtype=jnp.float32,
    )
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = LMTrainer(
            model,
            datasets,
            TrainConfig(
                epochs=epochs, batch_size=32, optimizer="adam",
                learning_rate=3e-3, log_frequency=10**9,
                checkpoint_dir=ckpt_dir,
            ),
            tokenizer=tok,
        )
        result = trainer.run()
        print(f"trained: perplexity {result['perplexity']:.2f}")

        # A fresh process would do exactly this — nothing below touches
        # the trainer: params come off disk through the canonical restore
        # layer, the vocab from the shipped tokenizer.json.
        server = TextServer.from_checkpoint(
            model,
            ckpt_dir,
            optimizer=trainer.optimizer,
            slots=4,
            chunk=16,
        )
        prompts = ["the model ", "one step ", "this data ", "a deep ",
                   "the fast ", "new node "]
        greedy = server.serve_text(prompts[:3], max_new=max_new)
        sampled = server.serve_text(
            prompts[3:], max_new=max_new, greedy=False, temperature=0.8,
            top_p=0.95, seed=7,
        )
        for p, g in zip(prompts[:3], greedy):
            print(f"greedy  {p!r} -> {g!r}")
        for p, s in zip(prompts[3:], sampled):
            print(f"nucleus {p!r} -> {s!r}")
    print("Done")


if __name__ == "__main__":
    configure_compile_cache()
    argv = [int(a) for a in sys.argv[1:3]]
    main(*argv)
