"""A tokenised corpus into a jitted training step as packed sequences.

One document a row, `input_ids: list<int32>`; FileReader.iter_device_batches(
lists="pack", seq_len=...) decodes the ids in device memory and cuts the
concatenated stream into fixed [sequences, seq_len] batches with segment ids
and positions, so that the step compiles once and attention and position
embeddings restart at document boundaries (README, "Packed token sequences").

Runs anywhere jax runs:
    JAX_PLATFORMS=cpu python examples/packed_sequences.py
"""

import os
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from parquet_tpu import FileReader  # enables x64 before any jnp array
import jax
import jax.numpy as jnp

VOCAB, EOS, SEQ_LEN, BATCH, WIDTH = 1000, 999, 256, 8, 32


def write_corpus(path: str) -> int:
    """Heavy-tailed documents, each ending in its EOS; three row groups."""
    rng = np.random.default_rng(0)
    lengths = np.clip(rng.lognormal(4.5, 1.0, 1500).astype(int), 4, 4 * SEQ_LEN)
    docs = [np.append(rng.integers(0, EOS, n - 1), EOS).tolist() for n in lengths]
    pq.write_table(pa.table({"input_ids": pa.array(docs, type=pa.list_(pa.int32()))}), path, row_group_size=500)
    return int(lengths.sum())


@jax.jit
def step(params, tokens, segment_ids, positions):
    """A stand-in for a model: embeddings by token and by position, one
    causal attention that stays inside a document's piece, a loss over the
    real slots. Everything a packed batch is for is used."""
    x = params["tokens"][tokens] + params["positions"][jnp.minimum(positions, SEQ_LEN - 1)]
    same_piece = (segment_ids[:, :, None] == segment_ids[:, None, :]) & (segment_ids[:, :, None] != 0)
    causal = jnp.tril(jnp.ones((SEQ_LEN, SEQ_LEN), bool))
    scores = jnp.where(same_piece & causal, x @ x.transpose(0, 2, 1) / WIDTH**0.5, -1e9)
    y = jax.nn.softmax(scores, axis=-1) @ x
    real = segment_ids != 0
    return jnp.sum(jnp.where(real, jnp.sum(y * y, axis=-1), 0.0)) / jnp.sum(real)


def main() -> None:
    rng = np.random.default_rng(1)
    params = {"tokens": jnp.asarray(rng.standard_normal((VOCAB, WIDTH)), jnp.float32) * 0.1,
              "positions": jnp.asarray(rng.standard_normal((SEQ_LEN, WIDTH)), jnp.float32) * 0.1}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "corpus.parquet")
        total = write_corpus(path)
        seen = steps = 0
        with FileReader(path) as r:
            # drop_remainder=True: every batch has the one shape the step compiled for
            for batch in r.iter_device_batches(BATCH, columns=["input_ids"], lists="pack", seq_len=SEQ_LEN):
                loss = step(params, *batch)
                seen += int(jnp.sum(batch.segment_ids != 0))
                steps += 1
    print(f"{steps} steps of [{BATCH}, {SEQ_LEN}] on {jax.devices()[0].platform}: {seen} of {total} tokens "
          f"(what is missing is the file's last, short batch), last loss {float(loss):.4f}")
    sequences = -(-total // SEQ_LEN)  # the file's last sequence is padded
    assert steps == sequences // BATCH and seen == min(total, steps * BATCH * SEQ_LEN)


if __name__ == "__main__":
    main()
