"""Plain reference for k-word proximity search, independent of the program.

It answers a query from the corpus's words alone, by the definitions of the
paper (Veretennikov, "An improved algorithm for fast K-word proximity
search based on multi-component key indexes") and of the system under
test, with nothing taken from the program: its own lemmatizer, its own
FL-list, its own key selection, and key records found by counting word
occurrences in windows instead of read from an index.

The semantics, step by step:

* words map to lemmas by a fixed English rule set (a word may have several
  lemmas, "are" -> are, be); a query expands into subqueries, one per
  combination of its words' lemmas (at most 16, in product order);
* lemmas rank by decreasing corpus frequency (ties by lemma); the first
  ``sw_count`` are stop lemmas, the next ``fu_count`` frequently used, the
  rest ordinary;
* each subquery is covered greedily by keys of up to three lemmas (the
  paper's section 6, with a duplicate lemma starred only once its
  multiplicity is met); a key's records are the occurrences of its
  components within ``max_distance`` of an occurrence of its first
  component, and only these key kinds hold records: three stop lemmas, two
  stop lemmas, one stop lemma, and a frequently used lemma with a later
  frequently used or an ordinary lemma;
* a document qualifies when every key of the subquery has a record in it;
  the occurrences that unstarred key components contribute form its event
  stream, and each event position at which every lemma of the subquery is
  present with its multiplicity ends a fragment that starts at the earliest
  of the lemmas' latest occurrences; fragments longer than
  ``2 * max_distance`` are dropped;
* the fragments of all subqueries are merged; a document scores the sum of
  ``1 / (span + 1) ** 2`` over its fragments in (start, end) order, and the
  top ``k`` documents are returned by decreasing score, then increasing id.
"""

from __future__ import annotations

import itertools
import re

import numpy as np

STOP, FREQUENT, ORDINARY = 0, 1, 2
SUBQUERY_LIMIT = 16

_TOKEN_RE = re.compile(r"[a-z0-9']+")

# word forms with irregular (or several) lemmas
_EXCEPTIONS: dict[str, tuple[str, ...]] = {
    "are": ("are", "be"), "is": ("be",), "am": ("be",), "was": ("be",),
    "were": ("be",), "been": ("be",), "being": ("be",), "has": ("have",),
    "had": ("have",), "having": ("have",), "does": ("do",), "did": ("do",),
    "done": ("do",), "doing": ("do",), "said": ("say",), "says": ("say",),
    "saying": ("say",), "went": ("go",), "gone": ("go",), "goes": ("go",),
    "found": ("find",), "me": ("i", "me"), "my": ("i", "my"), "you": ("you",),
    "your": ("you", "your"), "who": ("who",), "whom": ("who", "whom"),
    "what": ("what",), "men": ("man",), "women": ("woman",),
    "children": ("child",), "mice": ("mouse",), "feet": ("foot",),
    "teeth": ("tooth",), "made": ("make",), "making": ("make",),
    "took": ("take",), "taken": ("take",), "got": ("get",), "gotten": ("get",),
    "came": ("come",), "knew": ("know",), "known": ("know",),
    "thought": ("think",), "saw": ("see", "saw"), "seen": ("see",),
    "left": ("leave", "left"), "better": ("good", "better"),
    "best": ("good", "best"), "worse": ("bad", "worse"),
    "worst": ("bad", "worst"), "an": ("a",), "its": ("it",),
    "their": ("they", "their"), "them": ("they", "them"), "these": ("this",),
    "those": ("that",), "us": ("we", "us"), "songs": ("song",),
    "wars": ("war",), "times": ("time",),
}
# (suffix, replacement, least stem length), tried in order
_SUFFIXES: tuple[tuple[str, str, int], ...] = (
    ("iest", "y", 2), ("ies", "y", 2), ("sses", "ss", 2), ("shes", "sh", 2),
    ("ches", "ch", 2), ("xes", "x", 2), ("zes", "z", 2), ("ied", "y", 2),
    ("ing", "", 3), ("ingly", "", 3), ("edly", "", 3), ("ed", "", 3),
    ("est", "", 3), ("er", "", 3), ("ly", "", 3), ("s", "", 2),
)


def word_lemmas(word: str) -> tuple[str, ...]:
    """The lemmas of one lower-case word form."""
    if word in _EXCEPTIONS:
        return _EXCEPTIONS[word]
    if len(word) <= 3 or word.endswith("ss"):
        return (word,)
    for suffix, repl, least in _SUFFIXES:
        if word.endswith(suffix) and len(word) - len(suffix) >= least:
            stem = word[: len(word) - len(suffix)] + repl
            # undouble a final consonant: "running" -> "run"
            if len(stem) >= 3 and stem[-1] == stem[-2] and stem[-1] not in "aeiouslz":
                stem = stem[:-1]
            return (stem,)
    return (word,)


def select_keys(lemmas: tuple[str, ...], number) -> list[tuple[tuple[str, ...], tuple[bool, ...]]]:
    """Greedy key cover of one subquery: ``(components, starred)`` pairs in
    canonical order (FL-number, lemma, star).  ``number`` maps a lemma to
    its FL-number."""
    arity = min(3, len(lemmas))
    where: dict[str, list[int]] = {}
    for i, l in enumerate(lemmas):
        where.setdefault(l, []).append(i)
    mult = {l: len(ix) for l, ix in where.items()}
    unstarred = dict.fromkeys(where, 0)
    used: set[str] = set()
    order = lambda l: (number(l), l)
    keys = []
    while True:
        fresh = [l for l in where if l not in used]
        if not fresh:
            return keys
        first = min(fresh, key=order)
        comps, stars = [first], [False]
        used.add(first)
        unstarred[first] += 1
        taken = {where[first][0]}

        def free(l):
            return next((i for i in where[l] if i not in taken), None)

        for _ in range(1, arity):
            new = [l for l in where if l not in used and free(l) is not None]
            if new:
                pick = max(new, key=order)
                star = False
                used.add(pick)
            else:
                any_free = [l for l in where if free(l) is not None]
                if not any_free:
                    comps.append(max(where, key=order))
                    stars.append(True)
                    continue
                pick = max(any_free, key=order)
                star = unstarred[pick] >= mult[pick]
            if not star:
                unstarred[pick] += 1
            comps.append(pick)
            stars.append(star)
            taken.add(free(pick))
        slots = sorted(range(len(comps)), key=lambda i: (number(comps[i]), comps[i], stars[i]))
        keys.append((tuple(comps[i] for i in slots), tuple(stars[i] for i in slots)))


def _within(points: np.ndarray, centres: np.ndarray, d: int) -> np.ndarray:
    """How many of the sorted ``points`` lie within ``d`` of each centre."""
    return np.searchsorted(points, centres + d, "right") - np.searchsorted(points, centres - d, "left")


def _member(points: np.ndarray, values: np.ndarray) -> np.ndarray:
    if not len(points):
        return np.zeros(len(values), bool)
    at = np.searchsorted(points, values)
    return (at < len(points)) & (points[np.minimum(at, len(points) - 1)] == values)


class Reference:
    """Answers queries over one corpus (a :class:`bench.corpus.Corpus`)."""

    def __init__(self, corpus, *, sw_count: int, fu_count: int, max_distance: int):
        self.d = int(max_distance)
        lengths = corpus.doc_lengths()
        # documents lie STRIDE apart on one global position axis, so that no
        # window of any key or fragment reaches into a neighbouring document
        self.stride = int(lengths.max(initial=0)) + 8 * self.d + 8
        word_lem = [word_lemmas(w.lower()) for w in corpus.vocab]
        names = sorted({l for ls in word_lem for l in ls})
        lid = {l: i for i, l in enumerate(names)}
        n_lem = np.asarray([len(ls) for ls in word_lem], np.int64)
        flat_lem = np.asarray([lid[l] for ls in word_lem for l in ls], np.int64)
        first_lem = np.zeros(len(word_lem) + 1, np.int64)
        np.cumsum(n_lem, out=first_lem[1:])

        doc_of = np.repeat(np.arange(corpus.n_docs, dtype=np.int64), lengths)
        pos = np.arange(len(corpus.tokens), dtype=np.int64) - np.repeat(corpus.offsets[:-1], lengths)
        gpos = doc_of * self.stride + pos
        per_tok = n_lem[corpus.tokens]
        occ_pos = np.repeat(gpos, per_tok)
        k = np.arange(len(occ_pos)) - np.repeat(np.cumsum(per_tok) - per_tok, per_tok)
        occ_lem = flat_lem[np.repeat(first_lem[corpus.tokens], per_tok) + k]

        freq = np.bincount(occ_lem, minlength=len(names))
        ranked = sorted(range(len(names)), key=lambda i: (-freq[i], names[i]))
        ranked = [i for i in ranked if freq[i] > 0]
        self.fl_number = {names[i]: n for n, i in enumerate(ranked)}
        self.n_known = len(ranked)
        self.sw, self.fu = int(sw_count), int(fu_count)

        by = np.lexsort((occ_pos, occ_lem))
        occ_lem, occ_pos = occ_lem[by], occ_pos[by]
        bounds = np.searchsorted(occ_lem, np.arange(len(names) + 1))
        self._positions = {names[i]: occ_pos[bounds[i] : bounds[i + 1]] for i in range(len(names))}
        self._empty = np.empty(0, np.int64)

    # ---- lemma classes ----------------------------------------------------

    def number(self, lemma: str) -> int:
        return self.fl_number.get(lemma, self.n_known)

    def kind(self, lemma: str) -> int:
        n = self.fl_number.get(lemma)
        if n is None or n >= self.sw + self.fu:
            return ORDINARY
        return STOP if n < self.sw else FREQUENT

    def occurrences(self, lemma: str) -> np.ndarray:
        return self._positions.get(lemma, self._empty)

    # ---- key records --------------------------------------------------------

    def key_events(self, comps: tuple[str, ...]):
        """``(anchors, per-slot event positions)`` of one key's records, or
        None for a key kind that holds no records."""
        kinds = [self.kind(c) for c in comps]
        d = self.d
        p0 = self.occurrences(comps[0])
        if len(comps) == 1:
            return (p0, [p0]) if kinds[0] == STOP else None
        if len(comps) == 2:
            c0, c1 = comps
            if kinds == [STOP, STOP]:
                pass
            elif kinds[0] == FREQUENT and kinds[1] != STOP and c0 != c1:
                pass
            else:
                return None
            if c0 == c1:  # ordered pairs of occurrences, the later within d
                after = np.searchsorted(p0, p0 + d, "right") - np.searchsorted(p0, p0, "right")
                before = np.searchsorted(p0, p0, "left") - np.searchsorted(p0, p0 - d, "left")
                slot0 = p0[after >= 1]
                return slot0, [slot0, p0[before >= 1]]
            p1 = self.occurrences(c1)
            slot0 = p0[_within(p1, p0, d) >= 1]
            return slot0, [slot0, p1[_within(p0, p1, d) >= 1]]
        if kinds != [STOP, STOP, STOP]:
            return None
        c0, c1, c2 = comps
        p1, p2 = self.occurrences(c1), self.occurrences(c2)
        n1 = _within(p1, p0, d) - (c1 == c0)
        n2 = _within(p2, p0, d) - (c2 == c0)
        same12 = c1 == c2
        valid = n1 >= 2 if same12 else (n1 >= 1) & (n2 >= 1)
        anchors = p0[valid]
        # an occurrence fills slot 1 (2) when an anchor within d still has
        # a distinct occurrence for slot 2 (1) once it is taken
        q1 = p0[n1 >= 2] if same12 else p0[n2 >= 1]
        q2 = p0[n1 >= 2] if same12 else p0[n1 >= 1]
        e1 = _within(q1, p1, d) - ((c1 == c0) & _member(q1, p1))
        e2 = _within(q2, p2, d) - ((c2 == c0) & _member(q2, p2))
        return anchors, [anchors, p1[e1 >= 1], p2[e2 >= 1]]

    # ---- queries ------------------------------------------------------------

    def subqueries(self, query: str) -> list[tuple[str, ...]]:
        per_word = [word_lemmas(w) for w in _TOKEN_RE.findall(query.lower())]
        if not per_word:
            return []
        return list(itertools.islice(itertools.product(*per_word), SUBQUERY_LIMIT))

    def fragments(self, lemmas: tuple[str, ...]) -> np.ndarray:
        """Global ``(start, end)`` positions of one subquery's fragments."""
        mult: dict[str, int] = {}
        for l in lemmas:
            mult[l] = mult.get(l, 0) + 1
        events: dict[str, list[np.ndarray]] = {l: [] for l in mult}
        docs = None
        for comps, stars in select_keys(lemmas, self.number):
            found = self.key_events(comps)
            if found is None:
                return np.empty((0, 2), np.int64)
            anchors, slots = found
            key_docs = np.unique(anchors // self.stride)
            docs = key_docs if docs is None else np.intersect1d(docs, key_docs, assume_unique=True)
            for c, starred, at in zip(comps, stars, slots):
                if not starred:
                    events[c].append(at)
        if docs is None or not len(docs):
            return np.empty((0, 2), np.int64)
        stream = {}
        for l, parts in events.items():
            at = np.unique(np.concatenate(parts)) if parts else self._empty
            stream[l] = at[np.isin(at // self.stride, docs)]
        ends = np.unique(np.concatenate(list(stream.values())))
        doc_start = (ends // self.stride) * self.stride
        ok = np.ones(len(ends), bool)
        start = ends.copy()
        for l, m in mult.items():
            at = stream[l]
            upto = np.searchsorted(at, ends, "right")
            have = upto - np.searchsorted(at, doc_start, "left")
            ok &= have >= m
            latest = at[np.clip(upto - m, 0, max(len(at) - 1, 0))] if len(at) else ends
            start = np.minimum(start, np.where(have >= m, latest, start))
        ok &= ends - start <= 2 * self.d
        return np.stack([start[ok], ends[ok]], axis=1)

    def answer(self, query: str, top_k: int) -> list[tuple[int, float, list[tuple[int, int]]]]:
        """The top ``top_k`` documents: ``(doc_id, score, [(start, end)])``."""
        parts = [self.fragments(s) for s in self.subqueries(query)]
        parts = [p for p in parts if len(p)]
        if not parts or top_k <= 0:
            return []
        frags = np.unique(np.concatenate(parts), axis=0)  # sorted by (start, end)
        doc = frags[:, 0] // self.stride
        span = frags[:, 1] - frags[:, 0]
        approx = np.bincount(doc, weights=1.0 / (span + 1.0) ** 2)
        present = np.flatnonzero(np.bincount(doc))
        cut = np.sort(approx[present])[::-1][min(top_k, len(present)) - 1]
        # exact sums, in fragment order, for every document that can rank
        near = set(present[approx[present] >= cut * (1 - 1e-9)].tolist())
        picked = np.flatnonzero(np.isin(doc, list(near)))
        scored: dict[int, list] = {}
        for i in picked.tolist():
            scored.setdefault(int(doc[i]), []).append(int(span[i]))
        base = {dd: dd * self.stride for dd in scored}
        out = []
        for dd, spans in scored.items():
            out.append((dd, sum(1.0 / float(s + 1) ** 2 for s in spans)))
        out.sort(key=lambda t: (-t[1], t[0]))
        top = []
        for dd, score in out[:top_k]:
            rows = frags[doc == dd]
            top.append((dd, score, [(int(a - base[dd]), int(b - base[dd])) for a, b in rows]))
        return top
