"""Token data for the decoder-LM task: seeded synthetic documents with
heavy-tailed lengths, packed into fixed-length sequences.

What a real pre-training job's input looks like, as far as a run can feel
it (guide ``model-configs``, workloads.md, "Training: what a real job is
like"): the batch is counted in tokens, documents have heavy-tailed lengths
(log-normal here, a median of some hundreds of tokens and a tail past the
sequence length) and are packed end to end into rows of one length, so
packing waste and attention across document boundaries are real. There is
no network and no corpus here, so the tokens come from a process a model
can learn: a seeded bigram table whose marginals are Zipf over the
vocabulary. The loss then falls measurably within a few epochs, and since
some tokens are far more frequent than others, routing is uneven.

Every document ends with the end-of-document id (``EOD``, the vocabulary's
id 0). A row is ``seq_len + 2`` tokens: position ``i < seq_len`` reads
tokens ``0..i``, the main head is trained on token ``i + 1`` and the
multi-token-prediction module on token ``i + 2``. Packing is greedy and
lossless but for the stream's tail: documents are laid end to end and cut
where a row ends (the cut document continues in the next row), and what
does not fill the last row is dropped. ``packing_waste`` is the share of a
row's positions that are not document text: the ``EOD`` separators and the
dropped tail, over the tokens generated.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

EOD = 0


@dataclass
class TokenDataset:
    """Packed rows. ``train`` ``[n_train, seq_len + 2]`` and ``test``
    ``[n_test, seq_len + 2]`` int32, held out from one another (different
    documents of the same process)."""
    train: np.ndarray
    test: np.ndarray
    vocab_size: int
    seq_len: int
    packing_waste: float
    documents: int
    synthetic: bool = True

    # the trainers' generic view of a dataset
    @property
    def x_train(self) -> np.ndarray:
        return self.train

    @property
    def x_test(self) -> np.ndarray:
        return self.test


def bigram_table(vocab_size: int, seed: int, *, branch: int = 8,
                 zipf_a: float = 1.1):
    """``(successors [V, branch] int32, cumulative [V, branch] float64)``:
    each token's ``branch`` possible successors, drawn from a Zipf marginal
    over the vocabulary, and their cumulative probabilities (themselves
    Zipf over the branch). A model that learns the table predicts the
    likeliest successor about a third of the time."""
    r = np.random.default_rng([seed, 11])
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    marginal = ranks ** -zipf_a
    marginal /= marginal.sum()
    ids = r.permutation(vocab_size)          # which id has which rank
    successors = ids[r.choice(vocab_size, size=(vocab_size, branch),
                              p=marginal)].astype(np.int32)
    within = np.arange(1, branch + 1, dtype=np.float64) ** -1.5
    cumulative = np.tile(np.cumsum(within / within.sum()), (vocab_size, 1))
    return successors, cumulative


def generate_stream(n_tokens: int, vocab_size: int, seed: int, *,
                    median_len: float = 400.0, sigma: float = 1.2,
                    tag: int = 0):
    """At least ``n_tokens`` tokens of documents laid end to end, each
    followed by ``EOD``: ``(stream int32, documents, text_tokens)``.
    Lengths are log-normal (``median_len``, ``sigma``: with 400 and 1.2
    about 2.6% of documents pass 4,096 tokens and hold a fifth of the
    text). All documents advance together, one position a pass, so the
    cost is the longest document's length in numpy calls, not the
    stream's."""
    r = np.random.default_rng([seed, 12, tag])
    successors, cumulative = bigram_table(vocab_size, seed)
    lengths = np.empty((0,), np.int64)
    while lengths.sum() + len(lengths) < n_tokens:
        more = np.maximum(1, r.lognormal(np.log(median_len), sigma, size=max(
            16, int(n_tokens / median_len))).astype(np.int64))
        lengths = np.concatenate([lengths, more])
    ends = np.cumsum(lengths + 1)                    # each with its EOD
    lengths = lengths[:int(np.searchsorted(ends, n_tokens)) + 1]
    starts = np.cumsum(lengths + 1) - (lengths + 1)
    stream = np.full(int(starts[-1] + lengths[-1] + 1), EOD, np.int32)
    # ids 1.. are text; EOD never occurs inside a document
    current = r.integers(1, vocab_size, size=len(lengths)).astype(np.int32)
    alive = np.arange(len(lengths))
    for pos in range(int(lengths.max())):
        alive = alive[lengths[alive] > pos]
        stream[starts[alive] + pos] = current[alive]
        pick = (r.random(len(alive))[:, None]
                > cumulative[current[alive]]).sum(axis=1)
        nxt = successors[current[alive], np.minimum(
            pick, successors.shape[1] - 1)]
        current[alive] = np.where(nxt == EOD, 1, nxt)
    return stream, len(lengths), int(lengths.sum())


def pack(stream: np.ndarray, n_rows: int, seq_len: int) -> np.ndarray:
    """``[n_rows, seq_len + 2]``: row ``r`` is the stream from
    ``r * seq_len``; its two extra tokens are the next row's first two (the
    targets of its last positions)."""
    need = n_rows * seq_len + 2
    if len(stream) < need:
        raise ValueError(f"the stream holds {len(stream)} tokens, "
                         f"{n_rows} rows of {seq_len} need {need}")
    index = (np.arange(n_rows)[:, None] * seq_len
             + np.arange(seq_len + 2)[None])
    return stream[index]


def synthetic_documents(*, vocab_size: int, seq_len: int, n_train: int,
                        n_test: int, seed: int = 0,
                        median_len: float = 400.0,
                        sigma: float = 1.2) -> TokenDataset:
    """``n_train`` training rows and ``n_test`` held-out rows of the same
    bigram process (the same table, different documents)."""
    parts, docs, text, made = [], 0, 0, 0
    for tag, rows in ((1, n_train), (2, n_test)):
        stream, n_docs, n_text = generate_stream(
            rows * seq_len + 2, vocab_size, seed, median_len=median_len,
            sigma=sigma, tag=tag)
        parts.append(pack(stream, rows, seq_len))
        docs, made = docs + n_docs, made + len(stream)
        # text that landed in a row, not in the dropped tail
        used = stream[:rows * seq_len]
        text += int((used != EOD).sum())
    positions = (n_train + n_test) * seq_len
    return TokenDataset(
        train=parts[0], test=parts[1], vocab_size=vocab_size,
        seq_len=seq_len, packing_waste=1.0 - text / max(made, positions),
        documents=docs)


def make_token_batches(rows: np.ndarray, batch_size: int, *, seed: int = 0,
                       shuffle: bool = True):
    """Batches of whole rows, shuffled by ``seed``; the remainder is
    dropped."""
    order = (np.random.default_rng(seed).permutation(len(rows)) if shuffle
             else np.arange(len(rows)))
    for lo in range(0, len(rows) - batch_size + 1, batch_size):
        yield rows[order[lo:lo + batch_size]]
