"""Plain reference of the summarizer's preprocessing plan.

What the program computes with its plan (``where → drop_duplicates →
transform → where → fit_vocab → tokenize``) written here row by row in
plain Python, from the published description of each step and with
nothing imported from the program:

* keep rows whose title and abstract are both non-empty strings;
* drop exact duplicates of (title, abstract), keeping the first;
* clean: ASCII lowercase; delete ``<...>`` and then ``(...)`` spans (an
  opener raises the depth, a closer lowers it but not below 0, and only
  characters at depth 0 are kept; the brackets themselves never are);
  expand the contractions below in order; every character outside
  ``[a-z ]`` becomes a space; split into words. Abstracts then drop the
  English stopwords; both columns drop words of one letter;
* keep rows whose cleaned title and abstract are both non-empty;
* vocabulary: the specials ``<pad> <start> <end> <unk>`` and then the
  ``vocab_size - 4`` most frequent words of both cleaned columns, count
  descending and then word ascending;
* encoder row: abstract word ids cut to ``max_len``; decoder row:
  ``<start>``, title word ids cut to ``max_len - 2``, ``<end>``.
"""

from __future__ import annotations

import json
import re
from collections import Counter
from pathlib import Path
from typing import Iterable, Sequence

PAD, START, END, UNK = 0, 1, 2, 3
SPECIALS = ("<pad>", "<start>", "<end>", "<unk>")

STOPWORDS = frozenset(
    (
        "i me my myself we our ours ourselves you your yours yourself yourselves "
        "he him his himself she her hers herself it its itself they them their "
        "theirs themselves what which who whom this that these those am is are "
        "was were be been being have has had having do does did doing a an the "
        "and but if or because as until while of at by for with about against "
        "between into through during before after above below to from up down in "
        "out on off over under again further then once here there when where why "
        "how all any both each few more most other some such no nor not only own "
        "same so than too very s t can will just don should now"
    ).split()
)

CONTRACTIONS = (
    ("won't", "will not"),
    ("can't", "can not"),
    ("shan't", "shall not"),
    ("n't", " not"),
    ("'re", " are"),
    ("'ve", " have"),
    ("'ll", " will"),
    ("'m", " am"),
    ("'d", " would"),
    ("'s", ""),
    ("'", ""),
)

_LOWER = {c: c + 32 for c in range(ord("A"), ord("Z") + 1)}
_NOT_LETTER = re.compile(r"[^a-z ]")


def _strip_spans(text: str, open_c: str, close_c: str) -> str:
    marks = re.compile(re.escape(open_c) + "|" + re.escape(close_c))
    out, depth, last = [], 0, 0
    for m in marks.finditer(text):
        if depth == 0:
            out.append(text[last : m.start()])
        depth = depth + 1 if m.group() == open_c else max(depth - 1, 0)
        last = m.end()
    if depth == 0:
        out.append(text[last:])
    return "".join(out)


def clean_words(text: str, *, stopwords: bool) -> list[str]:
    """The cleaned word list of one field (abstract: ``stopwords=True``)."""
    text = text.translate(_LOWER)
    text = _strip_spans(text, "<", ">")
    text = _strip_spans(text, "(", ")")
    for pat, rep in CONTRACTIONS:
        text = text.replace(pat, rep)
    words = _NOT_LETTER.sub(" ", text).split()
    if stopwords:
        words = [w for w in words if w not in STOPWORDS]
    return [w for w in words if len(w) > 1]


def read_records(corpus_dir: str | Path) -> list[tuple[str, str]]:
    """(title, abstract) of every record, shards in name order."""
    rows = []
    for path in sorted(Path(corpus_dir).glob("*.jsonl")):
        with open(path, "rb") as fh:
            for line in fh:
                if line.strip():
                    rec = json.loads(line)
                    rows.append((rec.get("title"), rec.get("abstract")))
    return rows


def clean_corpus(corpus_dir: str | Path) -> list[tuple[list[str], list[str]]]:
    """(title words, abstract words) of every row the plan keeps."""
    seen: set = set()
    out = []
    for title, abstract in read_records(corpus_dir):
        if not (isinstance(title, str) and title and isinstance(abstract, str) and abstract):
            continue
        title, abstract = title.replace("\x00", " "), abstract.replace("\x00", " ")
        if (title, abstract) in seen:
            continue
        seen.add((title, abstract))
        t, a = clean_words(title, stopwords=False), clean_words(abstract, stopwords=True)
        if t and a:
            out.append((t, a))
    return out


def fit_vocab(rows: Iterable[tuple[list[str], list[str]]], vocab_size: int) -> dict[str, int]:
    """Word -> id over both cleaned columns."""
    counts: Counter = Counter()
    for t, a in rows:
        counts.update(t)
        counts.update(a)
    ranked = sorted(counts.items(), key=lambda kv: (-kv[1], kv[0]))
    words = list(SPECIALS) + [w for w, _ in ranked[: max(vocab_size - len(SPECIALS), 0)]]
    return {w: i for i, w in enumerate(words)}


def encode(words: Sequence[str], vocab: dict[str, int], max_len: int, *, start_end: bool = False) -> tuple[int, ...]:
    """Non-pad token ids of one field."""
    ids = [vocab.get(w, UNK) for w in words]
    if start_end:
        return (START, *ids[: max_len - 2], END)
    return tuple(ids[:max_len])


def token_rows(corpus_dir: str | Path, vocab_size: int, enc_len: int, dec_len: int):
    """The set of (encoder ids, decoder ids) rows the plan can emit, and
    the vocabulary fitted on the corpus."""
    rows = clean_corpus(corpus_dir)
    vocab = fit_vocab(rows, vocab_size)
    pairs = {
        (encode(a, vocab, enc_len), encode(t, vocab, dec_len, start_end=True))
        for t, a in rows
    }
    return pairs, vocab


def prompt_ids(text: str, vocab: dict[str, int], max_len: int) -> tuple[int, ...]:
    """A serving request's prompt: the encoder row of its abstract."""
    return encode(clean_words(text.replace("\x00", " "), stopwords=True), vocab, max_len)
