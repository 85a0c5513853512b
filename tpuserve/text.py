"""Text preprocessing: a self-contained WordPiece tokenizer (SURVEY.md §2 C3,
§3d "tokenize on host").

The reference serves image models; the build's text configs (BERT-base,
BASELINE.json config 3) need BERT-style tokenization. No network means no
pretrained tokenizer downloads, so this implements the standard BERT scheme
from scratch:

- Basic tokenization: NFD accent stripping, optional lowercasing, punctuation
  splitting, CJK isolation, whitespace split. A text that ``str.isascii()``
  leaves none of those Unicode questions open, so ONE compiled pattern splits
  it (``_ASCII_SPLIT``); any other text takes the per-character walk
  (``_walk``), whole. The choice is by the text and by nothing else, and the
  two give the same words letter for letter.
- WordPiece: greedy longest-match-first against a vocab, "##" continuations,
  [UNK] fallback. ``WordPieceTokenizer.ids`` looks each word up whole first
  (greedy search's own first probe) and searches only on a miss.

Vocabularies: ``WordPieceTokenizer.from_vocab_file`` loads a standard BERT
``vocab.txt`` (one token per line, id = line number). For no-artifact dev
serving, ``synthetic_vocab`` builds a deterministic vocab (special tokens,
printable ASCII pieces, common English subwords) so tokenization is stable
across processes without any file.

Tokenization runs on the host threadpool, per request and under the GIL: for
ASCII text one ``re`` call and one dictionary lookup a word, for the rest the
Python walk. The (ids, mask) arrays it emits are what crosses to the device.
"""

from __future__ import annotations

import re
import unicodedata

import numpy as np

PAD, UNK, CLS, SEP, MASK = "[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"
SPECIALS = (PAD, UNK, CLS, SEP, MASK)


def _is_punct(ch: str) -> bool:
    cp = ord(ch)
    if (33 <= cp <= 47) or (58 <= cp <= 64) or (91 <= cp <= 96) or (123 <= cp <= 126):
        return True
    return unicodedata.category(ch).startswith("P")


def _is_cjk(cp: int) -> bool:
    return (
        0x4E00 <= cp <= 0x9FFF or 0x3400 <= cp <= 0x4DBF
        or 0x20000 <= cp <= 0x2A6DF or 0xF900 <= cp <= 0xFAFF
    )


# What the walk does to ASCII, as one pattern: NFD is the identity, nothing is
# Mn or CJK, the separators are the code points below 33 and 127, and
# punctuation (each its own token) is 33-47, 58-64, 91-96 (\x60 is the
# backtick) and 123-126.
_ASCII_SPLIT = re.compile(r"[0-9A-Za-z]+|[!-/:-@\[-\x60{-~]").findall


def basic_tokenize(text: str, lower: bool = True) -> list[str]:
    """Whitespace/punctuation/CJK split with accent stripping."""
    if text.isascii():  # a flag CPython keeps: no scan
        return _ASCII_SPLIT(text.lower() if lower else text)
    return _walk(text, lower)


def _walk(text: str, lower: bool = True) -> list[str]:
    """The split a character at a time: the path for text that is not ASCII,
    and the oracle the tests hold the pattern to."""
    if lower:
        text = text.lower()
    text = unicodedata.normalize("NFD", text)
    out: list[str] = []
    word: list[str] = []

    def flush() -> None:
        if word:
            out.append("".join(word))
            word.clear()

    for ch in text:
        if unicodedata.category(ch) == "Mn":  # combining accent
            continue
        if ch.isspace():
            flush()
        elif _is_punct(ch) or _is_cjk(ord(ch)):
            flush()
            out.append(ch)
        elif ch == "\x00" or unicodedata.category(ch) == "Cc":
            flush()
        else:
            word.append(ch)
    flush()
    return out


class WordPieceTokenizer:
    """BERT-scheme tokenizer: basic split + greedy WordPiece."""

    def __init__(self, vocab: dict[str, int], lower: bool = True,
                 max_word_chars: int = 100) -> None:
        self.vocab = vocab
        self.lower = lower
        self.max_word_chars = max_word_chars
        for tok in SPECIALS:
            if tok not in vocab:
                raise ValueError(f"vocab is missing special token {tok}")
        self.pad_id = vocab[PAD]
        self.unk_id = vocab[UNK]
        self.cls_id = vocab[CLS]
        self.sep_id = vocab[SEP]
        self.inv = {i: t for t, i in vocab.items()}

    @classmethod
    def from_vocab_file(cls, path: str, lower: bool = True) -> "WordPieceTokenizer":
        """Standard BERT vocab.txt: one token per line, id = line index."""
        vocab: dict[str, int] = {}
        with open(path, encoding="utf-8") as f:
            for i, line in enumerate(f):
                tok = line.rstrip("\n")
                if tok:
                    vocab[tok] = i
        return cls(vocab, lower=lower)

    def wordpiece(self, word: str) -> list[str]:
        """Greedy longest-match-first split of one basic token."""
        if len(word) > self.max_word_chars:
            return [UNK]
        pieces: list[str] = []
        start = 0
        while start < len(word):
            end = len(word)
            cur = None
            while start < end:
                piece = word[start:end]
                if start > 0:
                    piece = "##" + piece
                if piece in self.vocab:
                    cur = piece
                    break
                end -= 1
            if cur is None:
                return [UNK]
            pieces.append(cur)
            start = end
        return pieces

    def tokenize(self, text: str) -> list[str]:
        out: list[str] = []
        for word in basic_tokenize(text, self.lower):
            out.extend(self.wordpiece(word))
        return out

    def ids(self, text: str) -> list[int]:
        """The ids of tokenize(text), without the piece strings: each word is
        looked up whole (wordpiece's own first probe) and only a miss, or a
        word over max_word_chars, goes through wordpiece."""
        vocab, longest = self.vocab, self.max_word_chars
        out: list[int] = []
        for word in basic_tokenize(text, self.lower):
            i = vocab.get(word)
            if i is not None and len(word) <= longest:
                out.append(i)
            else:
                out.extend(vocab[p] for p in self.wordpiece(word))
        return out

    def encode(self, text: str, max_len: int) -> tuple[np.ndarray, np.ndarray]:
        """Text -> ([CLS] pieces [SEP], mask), truncated+padded to max_len."""
        ids = [self.cls_id] + self.ids(text)
        ids = ids[: max_len - 1] + [self.sep_id]
        n = len(ids)
        arr = np.full((max_len,), self.pad_id, np.int32)
        arr[:n] = ids
        mask = np.zeros((max_len,), np.int32)
        mask[:n] = 1
        return arr, mask

    def n_tokens(self, text: str) -> int:
        """Sequence length encode() would need (incl. [CLS]/[SEP])."""
        return len(self.ids(text)) + 2


def synthetic_vocab(size: int = 8192, seed: int = 0) -> dict[str, int]:
    """Deterministic dev vocab: specials, ASCII chars (+## variants), common
    English subwords, then filler tokens up to `size`.

    Guarantees every ASCII string tokenizes without [UNK] (char fallback)."""
    toks: list[str] = list(SPECIALS)
    chars = [chr(c) for c in range(33, 127)] + list("0123456789")
    seen = set(toks)
    for c in [chr(c) for c in range(97, 123)] + [chr(c) for c in range(48, 58)] + chars:
        for t in (c, "##" + c):
            if t not in seen:
                seen.add(t)
                toks.append(t)
    common = (
        "the of and to in is was for on as with by at from it an be this that "
        "are or his her which not has had have but were they one all we can "
        "##s ##ed ##ing ##ly ##er ##est ##tion ##ment ##ness ##able ##ful "
        "time year day man world life hand part child eye woman place work "
        "week case point company number group problem fact model serve image "
        "text token batch size test run fast slow good new old high low"
    ).split()
    for t in common:
        if t not in seen:
            seen.add(t)
            toks.append(t)
    # The UNK-free guarantee needs every char+## piece above; never truncate
    # below them — clamp size up instead.
    size = max(size, len(toks))
    rng = np.random.default_rng(seed)
    letters = "abcdefghijklmnopqrstuvwxyz"
    while len(toks) < size:
        n = int(rng.integers(2, 6))
        t = "".join(letters[int(i)] for i in rng.integers(0, 26, n))
        if rng.random() < 0.5:
            t = "##" + t
        if t not in seen:
            seen.add(t)
            toks.append(t)
    return {t: i for i, t in enumerate(toks[:size])}


class CLIPBPETokenizer:
    """Byte-pair tokenizer for CLIP-family artifacts (SD 1.5 prompts).

    Real Stable Diffusion checkpoints pair the text encoder with OpenAI
    CLIP's byte-level BPE (vocab.json + merges.txt), not WordPiece. This
    wraps ``transformers.CLIPTokenizer`` (baked into the image; slow
    pure-python path, amortized by the decode threadpool) behind the same
    ``encode(text, max_len) -> (ids, mask)`` contract WordPiece exposes, so
    ``tpuserve.models.sd15`` swaps tokenizers by config alone.
    """

    def __init__(self, vocab_file: str, merges_file: str) -> None:
        from transformers import CLIPTokenizer

        self.tok = CLIPTokenizer(vocab_file=vocab_file, merges_file=merges_file)
        self.vocab: dict[str, int] = dict(self.tok.get_vocab())
        self.pad_id = int(self.tok.eos_token_id)  # CLIP pads with EOS
        self.bos_id = int(self.tok.bos_token_id)
        self.eos_id = int(self.tok.eos_token_id)

    def encode(self, text: str, max_len: int) -> tuple[np.ndarray, np.ndarray]:
        """Text -> (BOS ids EOS + EOS-padding, mask), fixed max_len."""
        out = self.tok(text, padding="max_length", truncation=True,
                       max_length=max_len)
        ids = np.asarray(out["input_ids"], np.int32)
        mask = np.asarray(out["attention_mask"], np.int32)
        return ids, mask

    def n_tokens(self, text: str) -> int:
        return len(self.tok(text)["input_ids"])
