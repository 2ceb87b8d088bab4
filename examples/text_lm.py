"""Text LM — train on deterministic English-like documents, then
generate text. The reference's only dataset is MNIST images
(reference tfsingle.py:13-14); this drives the framework's text story
end to end: tokenizer → pack_documents → LMTrainer lifecycle →
greedy / nucleus / beam generation decoded back to strings.

Byte-level by default; pass a merge count to train a BPE vocabulary on
the corpus first (native incremental trainer, data/text.py) — the same
documents then pack into fewer, higher-entropy tokens, and the learned
vocab is saved alongside any checkpoint the trainer writes.

Run: ``python examples/text_lm.py [epochs] [max_new] [bpe_merges]``
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_tpu.config import TrainConfig
from distributed_tensorflow_tpu.data import (
    BPETokenizer,
    ByteTokenizer,
    synthetic_documents,
    text_corpus,
)
from distributed_tensorflow_tpu.models.gpt import GPTLM
from distributed_tensorflow_tpu.train import LMTrainer
from distributed_tensorflow_tpu.utils.compile_cache import (
    configure_compile_cache,
)


def main(epochs: int = 6, max_new: int = 48, bpe_merges: int = 0) -> None:
    if bpe_merges:
        t0 = time.perf_counter()
        tok = BPETokenizer.train(
            synthetic_documents(768, seed=0), num_merges=bpe_merges
        )
        print(
            f"trained {len(tok.merges)}-merge BPE vocab "
            f"({tok.vocab_size} ids) in {time.perf_counter() - t0:.2f}s"
        )
    else:
        tok = ByteTokenizer()
    datasets = text_corpus(
        num_docs=768, seq_len=96, n_val=16, n_test=16, seed=0, tokenizer=tok
    )
    model = GPTLM(
        vocab_size=tok.vocab_size,
        max_len=96 + max_new,
        model_dim=96,
        num_heads=4,
        num_layers=3,
        compute_dtype=jnp.float32,
    )
    trainer = LMTrainer(
        model,
        datasets,
        TrainConfig(
            epochs=epochs, batch_size=32, optimizer="adam",
            learning_rate=3e-3, log_frequency=20,
        ),
        tokenizer=tok,
    )
    result = trainer.run()
    print(f"held-out perplexity: {result['perplexity']:.2f} (uniform = {tok.vocab_size})")

    params = trainer.state.params
    prompt = jnp.asarray(tok.encode("the model ")[None, :], jnp.int32)
    greedy = model.greedy_decode(params, prompt, max_new)
    nucleus = model.sample_decode(
        params, prompt, max_new, jax.random.key(0), temperature=0.8, top_p=0.95
    )
    beam = model.beam_decode(params, prompt, max_new, 4, eos_id=tok.eos_id)
    print(f"greedy:  {tok.decode(np.asarray(greedy)[0])!r}")
    print(f"nucleus: {tok.decode(np.asarray(nucleus)[0])!r}")
    print(f"beam-4:  {tok.decode(np.asarray(beam)[0])!r}")
    print("Done")


if __name__ == "__main__":
    configure_compile_cache()
    argv = [int(a) for a in sys.argv[1:4]]
    main(*argv)
