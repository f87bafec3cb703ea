"""The benchmark's token data: seeded synthetic documents packed into rows.

The construction is ``data/tokens.py``'s (a bigram table with Zipf
marginals, log-normal document lengths, documents laid end to end with an
end-of-document id and cut into rows of ``seq_len + 2``), kept here so that
the yardstick's inputs cannot change under it, as ``harness/data.py`` keeps
the images'. :func:`make_token_dataset` hands the program its
``TokenDataset``.
"""

from __future__ import annotations

import numpy as np

EOD = 0


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


def make_token_dataset(config: dict, traffic: dict, n_train: int, seed: int):
    """The program's ``TokenDataset`` for a configuration file, a traffic
    mix and a number of training rows: rows of the mix's ``seq_len`` from
    the configuration's ``data`` process, ``eval.held_out_sequences``
    held-out rows, ids below the configuration's (sliced) ``vocab_size``."""
    from distributed_parameter_server_for_ml_training_tpu.data.tokens \
        import TokenDataset
    data = config["data"]
    if data["kind"] != "bigram_documents":
        raise ValueError(f"unknown data kind {data['kind']!r}")
    vocab, seq_len = int(config["vocab_size"]), int(traffic["seq_len"])
    n_test = int(config["eval"]["held_out_sequences"])
    parts, docs, text, made = [], 0, 0, 0
    for tag, rows in ((1, n_train), (2, n_test)):
        stream, n_docs, _n_text = generate_stream(
            rows * seq_len + 2, vocab, seed,
            median_len=float(data["median_len"]),
            sigma=float(data["sigma"]), tag=tag)
        parts.append(pack(stream, rows, seq_len))
        docs, made = docs + n_docs, made + len(stream)
        text += int((stream[:rows * seq_len] != EOD).sum())
    return TokenDataset(
        train=parts[0], test=parts[1], vocab_size=vocab, seq_len=seq_len,
        packing_waste=1.0 - text / max(made, (n_train + n_test) * seq_len),
        documents=docs)
