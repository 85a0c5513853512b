"""The benchmark's vocabulary and its own whole-word tokenization.

No published `vocab.txt` is in the container, so the benchmark writes one of
the published size and layout (google-research/bert uncased: [PAD] at 0,
[unused*], [UNK] 100, [CLS] 101, [SEP] 102, [MASK] 103, then the entries) whose
entries are whole lower-case words. A text made of these words, joined by
single spaces, has exactly one token per word under any WordPiece tokenizer
(greedy longest match finds the whole word first), so the generator and the
reference know a text's ids without running the program's tokenizer.

The table is fixed (it stands for a published file), not drawn from --seed.
"""

from __future__ import annotations

import numpy as np

PAD, UNK, CLS, SEP, MASK = 0, 100, 101, 102, 103
FIRST_WORD = 104
_LETTERS = "abcdefghijklmnopqrstuvwxyz"


def make_vocab(size: int) -> list[str]:
    """`size` distinct entries; ids >= FIRST_WORD are words of 2 to 10 letters
    (mean about 5, an English text's)."""
    if size <= FIRST_WORD:
        raise ValueError(f"vocabulary of {size} has no room for words")
    toks = [f"[unused{i}]" for i in range(FIRST_WORD)]
    toks[PAD], toks[UNK], toks[CLS], toks[SEP], toks[MASK] = (
        "[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]")
    rng = np.random.default_rng(30522)
    seen: set[str] = set()
    while len(toks) < size:
        n = int(np.clip(rng.poisson(3.0) + 2, 2, 10))
        w = "".join(_LETTERS[i] for i in rng.integers(0, 26, n))
        if w not in seen:
            seen.add(w)
            toks.append(w)
    return toks


def write_vocab(path: str, vocab: list[str]) -> None:
    with open(path, "w", encoding="utf-8") as f:
        f.write("\n".join(vocab) + "\n")


def encode(text: str, word_id: dict[str, int]) -> np.ndarray:
    """[CLS] one id per word [SEP]; a word outside the table is an error, not
    [UNK]: the generator only emits words of the table."""
    return np.asarray([CLS] + [word_id[w] for w in text.split(" ")] + [SEP],
                      np.int32)
