"""Synthetic corpora for the benchmark's configurations.

A Zipf-distributed stand-in for a public collection: an English
function-word head at its real frequency ranks, a tail of synthetic content
words, and the paper's example phrases injected at a small rate, so that
stop-word-heavy queries have non-trivial answers.  The distribution follows
the program's own ``synthesize_corpus`` (kept here so that no change to the
program changes the benchmark's data), vectorized, and with a document
length distribution: ``fixed``, or ``lognormal`` with no cap.

A corpus is held as flat word ids over ``vocab`` with per-document offsets;
``texts()`` renders the documents the program ingests.  The same
configuration always gives the same corpus.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

# Head of the English frequency distribution, in rough Zipf rank order.
FUNCTION_WORDS: tuple[str, ...] = (
    "the", "be", "to", "of", "and", "a", "in", "that", "have", "i",
    "it", "for", "not", "on", "with", "he", "as", "you", "do", "at",
    "this", "but", "his", "by", "from", "they", "we", "say", "her", "she",
    "or", "an", "will", "my", "one", "all", "would", "there", "their", "what",
    "so", "up", "out", "if", "about", "who", "get", "which", "go", "me",
    "when", "make", "can", "like", "time", "no", "just", "him", "know", "take",
    "people", "into", "year", "your", "good", "some", "could", "them", "see", "other",
    "than", "then", "now", "look", "only", "come", "its", "over", "think", "also",
    "back", "after", "use", "two", "how", "our", "work", "first", "well", "way",
    "even", "new", "want", "because", "any", "these", "give", "day", "most", "us",
    "is", "are", "was", "were", "why", "need", "war", "man", "old", "great",
)

# The paper's running examples, injected so that its queries have answers.
PHRASES: tuple[str, ...] = (
    "who are you who",
    "to be or not to be",
    "who are you and why did you say what you did",
    "the who are an english rock band",
    "i need you",
    "one at a time",
    "who is who in the world of war",
    "what do you do all day",
    "how to find the mean",
    "time and time again",
)

# The paper's two example documents lead every corpus (doc ids 0 and 1).
PAPER_EXAMPLE_DOCS: tuple[str, ...] = (
    "who are you is the album by the who",
    "who has reality who is real who is true",
)


@dataclass
class Corpus:
    """Documents as word ids: document ``d`` is
    ``tokens[offsets[d]:offsets[d + 1]]`` over ``vocab``."""

    vocab: list[str]
    tokens: np.ndarray  # int32 word ids, all documents back to back
    offsets: np.ndarray  # int64, n_docs + 1

    @property
    def n_docs(self) -> int:
        return len(self.offsets) - 1

    def doc_lengths(self) -> np.ndarray:
        return np.diff(self.offsets)

    def texts(self) -> list[str]:
        words = np.asarray(self.vocab, dtype=object)[self.tokens]
        off = self.offsets.tolist()
        return [" ".join(words[off[d] : off[d + 1]]) for d in range(self.n_docs)]

    def doc_words(self, d: int) -> list[str]:
        return [self.vocab[w] for w in self.tokens[self.offsets[d] : self.offsets[d + 1]]]

    def save(self, path) -> None:
        np.savez(path, vocab=np.asarray(self.vocab), tokens=self.tokens, offsets=self.offsets)

    @classmethod
    def load(cls, path) -> "Corpus":
        with np.load(path) as z:
            return cls(vocab=[str(w) for w in z["vocab"]], tokens=z["tokens"], offsets=z["offsets"])


def _draw_lengths(rng: np.random.Generator, n_docs: int, length: dict) -> np.ndarray:
    kind = length["kind"]
    if kind == "fixed":
        return np.full(n_docs, int(length["tokens"]), np.int64)
    if kind == "lognormal":
        # mean of a lognormal is exp(mu + sigma^2 / 2)
        sigma = float(length["sigma"])
        mu = np.log(float(length["mean"])) - sigma * sigma / 2
        raw = rng.lognormal(mu, sigma, size=n_docs)
        return np.maximum(np.rint(raw), int(length["min"])).astype(np.int64)
    raise ValueError(f"unknown document length kind {kind!r}")


def synthesize(
    *,
    n_docs: int,
    vocab_size: int,
    zipf_a: float,
    phrase_rate: float,
    length: dict,
    seed: int,
) -> Corpus:
    """``n_docs`` documents plus the two paper examples.  Each document
    draws its length from ``length``, then that many words from the Zipf
    vocabulary; before each word, with probability ``phrase_rate``, one of
    the paper's phrases is injected whole (so a document may run over its
    drawn length by a phrase, as in the program's generator)."""
    rng = np.random.default_rng(seed)
    vocab = list(FUNCTION_WORDS) + [f"w{i:05d}" for i in range(vocab_size)]
    n_zipf = len(vocab)
    index = {w: i for i, w in enumerate(vocab)}
    for text in PHRASES + PAPER_EXAMPLE_DOCS:
        for w in text.split():
            if w not in index:
                index[w] = len(vocab)
                vocab.append(w)
    phrase_ids = [np.asarray([index[w] for w in p.split()], np.int32) for p in PHRASES]
    phrase_len = np.asarray([len(p) for p in phrase_ids], np.int64)

    ranks = np.arange(1, n_zipf + 1, dtype=np.float64)
    probs = ranks ** (-zipf_a)
    probs /= probs.sum()

    lengths = _draw_lengths(rng, n_docs, length)
    total = int(lengths.sum())
    draws = rng.choice(n_zipf, size=total, p=probs).astype(np.int32)
    inject = rng.random(total) < phrase_rate
    which = rng.integers(len(PHRASES), size=total)
    emitted = 1 + np.where(inject, phrase_len[which], 0)
    starts = np.zeros(total, np.int64)
    np.cumsum(emitted[:-1], out=starts[1:])
    out = np.empty(int(emitted.sum()), np.int32)
    out[starts + emitted - 1] = draws
    for p, ids in enumerate(phrase_ids):
        hit = starts[inject & (which == p)]
        for k, w in enumerate(ids):
            out[hit + k] = w

    doc_draw_end = np.cumsum(lengths)
    doc_end = np.concatenate([[0], (starts + emitted)[doc_draw_end - 1]])
    docs = [out[doc_end[d] : doc_end[d + 1]] for d in range(n_docs)]
    head = [np.asarray([index[w] for w in t.split()], np.int32) for t in PAPER_EXAMPLE_DOCS]
    all_docs = head + docs
    offsets = np.zeros(len(all_docs) + 1, np.int64)
    np.cumsum([len(d) for d in all_docs], out=offsets[1:])
    return Corpus(vocab=vocab, tokens=np.concatenate(all_docs).astype(np.int32), offsets=offsets)
