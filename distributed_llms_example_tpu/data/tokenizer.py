"""Tokenizers.

The reference downloads ``AutoTokenizer.from_pretrained(model_ckpt)`` from
the HF hub (reference train-torchrun.py:34).  This framework runs in
zero-egress environments, so tokenization is pluggable:

- ``HFTokenizer`` wraps a tokenizer loaded from *local* files (a checkpoint
  directory shipped as a platform input, the same mechanism the reference
  uses for datasets);
- ``ByteTokenizer`` is a dependency-free byte-level fallback (UTF-8 bytes
  shifted past the special ids) that makes every pipeline runnable and
  testable with no assets at all.
"""

from __future__ import annotations

import contextlib as _contextlib
from typing import Protocol, Sequence


class Tokenizer(Protocol):
    """Role-based encoding: the SPECIAL-TOKEN LAYOUT is the tokenizer's
    job, not the dataset's.  Each model family lays out sequences its own
    way (BART ``<s>…</s>``, T5 ``…</s>``, LLaMA ``<s>…``), and a
    home-grown "append one EOS" convention silently mismatches the
    pretraining format when fine-tuning real checkpoints — so datasets ask
    for ids by ROLE and the tokenizer applies the family's layout."""

    vocab_size: int
    pad_id: int
    eos_id: int

    def encode(self, text: str) -> list[int]:
        """Plain content ids — no special tokens, no truncation."""
        ...

    def encode_source(self, text: str, max_length: int) -> list[int]:
        """Seq2seq encoder input, family layout applied, ≤ max_length."""
        ...

    def encode_target(self, text: str, max_length: int) -> list[int]:
        """Seq2seq decoder labels, family layout applied, ≤ max_length."""
        ...

    def encode_prompt(self, text: str, max_length: int) -> list[int]:
        """Causal-LM prompt prefix (loss-masked): leading specials only."""
        ...

    def encode_continuation(self, text: str, max_length: int) -> list[int]:
        """Causal-LM continuation: content + end-of-sequence, no BOS."""
        ...

    def decode(self, ids: Sequence[int]) -> str: ...

    def encode_source_batch(self, texts: Sequence[str], max_length: int) -> list[list[int]]:
        """Batch form of ``encode_source`` — id-identical, but tokenizers
        with a parallel batch path (HF fast tokenizers: Rust + rayon
        across all cores) encode the whole list at once.  One prefetch
        thread tokenizing example-by-example caps out near 200k tok/s —
        well short of the ~480k tok/s a v5e-8 host must assemble — so the
        datasets fill their caches through this entry point per batch."""
        ...

    def encode_target_batch(self, texts: Sequence[str], max_length: int) -> list[list[int]]: ...


class ByteTokenizer:
    """UTF-8 bytes + {pad=0, eos=1}; ids are byte+2.  Its "family layout"
    is the framework's own: sources/targets end in one EOS, prompts carry
    no specials at all."""

    OFFSET = 2

    def __init__(self) -> None:
        self.pad_id = 0
        self.eos_id = 1
        self.vocab_size = 256 + self.OFFSET

    def encode(self, text: str) -> list[int]:
        return [b + self.OFFSET for b in text.encode("utf-8")]

    def encode_source(self, text: str, max_length: int) -> list[int]:
        return self.encode(text)[: max_length - 1] + [self.eos_id]

    encode_target = encode_source
    encode_continuation = encode_source

    def encode_prompt(self, text: str, max_length: int) -> list[int]:
        return self.encode(text)[:max_length]

    def encode_source_batch(self, texts: Sequence[str], max_length: int) -> list[list[int]]:
        # byte encoding is memory-bandwidth work; a plain loop already
        # clears the pod-host feed rate with >10x margin (BASELINE.md)
        return [self.encode_source(t, max_length) for t in texts]

    encode_target_batch = encode_source_batch

    def decode(self, ids: Sequence[int]) -> str:
        # ids outside [OFFSET, OFFSET+256) are skipped, not an error: models
        # may have a larger vocab than the tokenizer (padded/rounded vocab
        # sizes), and randomly-initialized models emit arbitrary ids
        data = bytes(i - self.OFFSET for i in ids if self.OFFSET <= i < self.OFFSET + 256)
        return data.decode("utf-8", errors="replace")


@_contextlib.contextmanager
def _rust_parallelism():
    """Enable the Rust tokenizer's rayon parallelism for the duration of
    ONE batch call.  Setting TOKENIZERS_PARALLELISM=true process-wide
    would also disable the library's fork-detected auto-shutoff — a
    forked child (e.g. an embedder's fork-based multiprocessing) could
    then deadlock on the poisoned rayon pool.  Scoping the variable to
    the call keeps the batch path parallel AND the safety net intact; an
    explicit user setting (either value) always wins."""
    import os

    if os.environ.get("TOKENIZERS_PARALLELISM") is not None:
        yield
        return
    os.environ["TOKENIZERS_PARALLELISM"] = "true"
    try:
        yield
    finally:
        os.environ.pop("TOKENIZERS_PARALLELISM", None)


class HFTokenizer:
    """A Hugging Face tokenizer loaded from a local directory.

    Layout-bearing roles delegate to the HF tokenizer itself — its
    post-processor IS the family's special-token layout (BART's
    ``<s>…</s>``, T5's ``…</s>``, LLaMA's BOS-only), and HF truncation
    keeps the trailing specials — so ids match
    ``AutoTokenizer.__call__(text, max_length=…, truncation=True)``
    exactly (the reference recipe, reference train-accelerator.py:114-133;
    parity test: tests/test_tokenizer_parity.py)."""

    def __init__(self, path: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(path, local_files_only=True)
        self.vocab_size = len(self._tok)
        self.pad_id = self._tok.pad_token_id if self._tok.pad_token_id is not None else 0
        # _has_eos gates EOS-aware layout edits below: when the loaded
        # tokenizer defines no eos_token, the fallback id 1 is just an
        # ordinary vocab token and must be neither stripped nor appended
        self._has_eos = self._tok.eos_token_id is not None
        self.eos_id = self._tok.eos_token_id if self._has_eos else 1

    def encode(self, text: str) -> list[int]:
        return self._tok.encode(text, add_special_tokens=False)

    def encode_source(self, text: str, max_length: int) -> list[int]:
        return self._tok(text, max_length=max_length, truncation=True)["input_ids"]

    def encode_target(self, text: str, max_length: int) -> list[int]:
        # text_target routes through the target-side post-processor (for
        # BART/T5 identical to the source side; kept distinct for families
        # where it differs) — the reference's `text_target=` call path
        return self._tok(text_target=text, max_length=max_length, truncation=True)["input_ids"]

    def encode_prompt(self, text: str, max_length: int) -> list[int]:
        # a causal prompt keeps its leading specials (LLaMA's BOS) but must
        # NOT end the document — strip any trailing EOS the layout added
        ids = self._tok(text, max_length=max_length, truncation=True)["input_ids"]
        while self._has_eos and ids and ids[-1] == self.eos_id:
            ids = ids[:-1]
        return ids

    def encode_continuation(self, text: str, max_length: int) -> list[int]:
        # continuation of an already-started document: content ids only
        # (a BOS here would be a mid-sequence document restart) + EOS
        ids = self._tok.encode(text, add_special_tokens=False)
        if not self._has_eos:
            return ids[:max_length]
        return ids[: max_length - 1] + [self.eos_id]

    def encode_source_batch(self, texts: Sequence[str], max_length: int) -> list[list[int]]:
        # one call into the Rust tokenizer: rayon fans the batch across
        # cores and the ids are exactly the per-text encode_source ids
        with _rust_parallelism():
            return self._tok(list(texts), max_length=max_length, truncation=True)["input_ids"]

    def encode_target_batch(self, texts: Sequence[str], max_length: int) -> list[list[int]]:
        with _rust_parallelism():
            return self._tok(
                text_target=list(texts), max_length=max_length, truncation=True
            )["input_ids"]

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode([i for i in ids], skip_special_tokens=True)


def get_tokenizer(spec: str, model_ckpt: str = "") -> Tokenizer:
    """Resolve a tokenizer spec: explicit path > model checkpoint dir > byte."""
    import os

    if spec and spec != "byte":
        return HFTokenizer(spec)
    if spec == "byte":
        return ByteTokenizer()
    if model_ckpt and os.path.isdir(model_ckpt):
        try:
            return HFTokenizer(model_ckpt)
        except Exception:
            pass
    return ByteTokenizer()
