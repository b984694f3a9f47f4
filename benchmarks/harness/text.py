"""Seeded synthetic text and its byte tokenization, the benchmark's own.

There are no tokenizer files offline, so every cell runs the byte tokenizer
(stated under ``assumed`` in the configuration files).  Its rule is
re-implemented here (UTF-8 byte + 2, pad 0, one EOS = 1 closing a source or
a target, truncated to the maximum length) so that what the program's input
pipeline feeds the step can be checked row by row against it.
"""

from __future__ import annotations

import numpy as np

BYTE_OFFSET, PAD_ID, EOS_ID, LABEL_PAD = 2, 0, 1, -100


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed is valid."""
    return np.random.default_rng([int(seed), int(stream)])


def words_text(rng: np.random.Generator, n_chars: int) -> str:
    """Lower-case pseudo-words separated by spaces, exactly ``n_chars`` long."""
    out, total = [], 0
    while total - 1 < n_chars:  # the joined text is one separator shorter than the sum
        w = "".join(chr(97 + int(c)) for c in rng.integers(0, 26, size=int(rng.integers(2, 10))))
        out.append(w)
        total += len(w) + 1
    return " ".join(out)[:n_chars]


def encode(text: str, max_length: int) -> list[int]:
    """Source/target layout: bytes + offset, truncated, one closing EOS."""
    return [b + BYTE_OFFSET for b in text.encode("utf-8")][: max_length - 1] + [EOS_ID]


def summarize_records(seed: int, n: int, *, source_chars: int, target_tokens: list[int]) -> list[dict]:
    """``n`` dialogue/summary records.  Every source is ``source_chars`` long
    (over the source width, so it truncates to it); target ``i`` encodes to
    ``target_tokens[perm[i]]`` tokens with its EOS: the multiset of lengths
    is the same for every seed, the order and the text are the seed's."""
    rng = rng_for(seed, 1)
    lengths = [target_tokens[int(j)] for j in rng.permutation(len(target_tokens))]
    return [
        {"dialogue": words_text(rng, source_chars), "summary": words_text(rng, lengths[i % len(lengths)] - 1)}
        for i in range(n)
    ]


def expected_rows(records: list[dict], max_source: int, max_target: int, tgt_width: int) -> dict:
    """What one record must look like in a fed batch, keyed by the bytes of
    its ``input_ids`` row: (attention_mask row, labels row)."""
    rows = {}
    for r in records:
        src = encode(r["dialogue"], max_source)
        tgt = encode(r["summary"], max_target)
        ids = np.full(max_source, PAD_ID, np.int32)
        ids[: len(src)] = src
        mask = np.zeros(max_source, np.int32)
        mask[: len(src)] = 1
        labels = np.full(tgt_width, LABEL_PAD, np.int32)
        labels[: len(tgt)] = tgt
        rows[ids.tobytes()] = (mask, labels)
    return rows
