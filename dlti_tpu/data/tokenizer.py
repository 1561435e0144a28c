"""Tokenizer layer.

The reference uses HF ``AutoTokenizer`` (Rust ``tokenizers`` backend,
``train_baseline.py:115-117``) with pad=eos fallback. We wrap the same
data-plane (tokenization is host-side on GPU and TPU alike) and add a
hermetic :class:`ByteTokenizer` so tests and offline environments never need
the HF hub.
"""

from __future__ import annotations

from typing import List, Optional, Protocol


class Tokenizer(Protocol):
    vocab_size: int
    pad_id: int
    eos_id: int
    bos_id: int

    def encode(self, text: str, add_bos: bool = False, add_eos: bool = False) -> List[int]: ...
    def decode(self, ids: List[int]) -> str: ...


class ByteTokenizer:
    """UTF-8 byte tokenizer with BOS/EOS/PAD specials — hermetic, vocab 259.

    id 0 = pad, 1 = bos, 2 = eos, byte b -> b + 3.
    """

    def __init__(self) -> None:
        self.pad_id = 0
        self.bos_id = 1
        self.eos_id = 2
        self.vocab_size = 259

    def encode(self, text: str, add_bos: bool = False, add_eos: bool = False) -> List[int]:
        ids = [b + 3 for b in text.encode("utf-8")]
        if add_bos:
            ids = [self.bos_id] + ids
        if add_eos:
            ids = ids + [self.eos_id]
        return ids

    def decode(self, ids: List[int]) -> str:
        # Ignore specials and out-of-vocab ids (a serving model's vocab may
        # exceed 259; decode must never raise on sampled ids).
        data = bytes(i - 3 for i in ids if 3 <= i < 259)
        return data.decode("utf-8", errors="replace")


class IdTokenizer:
    """Hermetic id-passthrough tokenizer: every id renders as ``<id> `` and
    text encodes by parsing that form (non-numeric words hash into the
    vocab). Exists for serving benchmarks against random-weight models,
    whose sampled ids exceed any real tokenizer's printable range — the
    byte tokenizer renders those as empty strings, which suppresses every
    SSE delta and zeroes streaming TTFT/TPOT measurements.
    """

    def __init__(self, vocab_size: int = 32000) -> None:
        self.pad_id = 0
        self.bos_id = 1
        self.eos_id = 2
        self.vocab_size = vocab_size

    def encode(self, text: str, add_bos: bool = False, add_eos: bool = False) -> List[int]:
        ids = []
        for w in text.split():
            if w.startswith("<") and w.endswith(">") and w[1:-1].isdigit():
                ids.append(int(w[1:-1]) % self.vocab_size)
            else:
                import zlib

                # crc32, not hash(): stable across processes (PYTHONHASHSEED).
                ids.append(3 + (zlib.crc32(w.encode()) % (self.vocab_size - 3)))
        if add_bos:
            ids = [self.bos_id] + ids
        if add_eos:
            ids = ids + [self.eos_id]
        return ids

    def decode(self, ids: List[int]) -> str:
        return " ".join(f"<{i}>" for i in ids)

    def decode_appended(self, text: str, ids: List[int]) -> str:
        """``decode(ids)``, given ``text == decode(ids[:-1])``. A stream
        that decodes its whole answer anew at every token pays the
        answer's length a token under the interpreter lock (58 us at 448
        ids, 32 streams a round); a tokenizer whose text is a join over
        its tokens can say so by having this method, and pays one piece.
        ``ByteTokenizer`` and ``HFTokenizer`` cannot (a character may span
        tokens) and have none."""
        piece = f"<{ids[-1]}>"
        return f"{text} {piece}" if len(ids) > 1 else piece


class HFTokenizer:
    """Adapter over a HF fast tokenizer (pad=eos fallback like
    ``train_baseline.py:116-117``)."""

    def __init__(self, name_or_path: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(name_or_path)
        if self._tok.pad_token is None:
            self._tok.pad_token = self._tok.eos_token
        self.vocab_size = len(self._tok)
        self.pad_id = self._tok.pad_token_id
        self.eos_id = self._tok.eos_token_id
        self.bos_id = (
            self._tok.bos_token_id if self._tok.bos_token_id is not None else self.eos_id
        )

    def encode(self, text: str, add_bos: bool = False, add_eos: bool = False) -> List[int]:
        ids = self._tok.encode(text, add_special_tokens=False)
        if add_bos:
            ids = [self.bos_id] + ids
        if add_eos:
            ids = ids + [self.eos_id]
        return ids

    def decode(self, ids: List[int]) -> str:
        return self._tok.decode(ids, skip_special_tokens=True)


def get_tokenizer(name: str) -> Tokenizer:
    """"byte" / "id[:vocab]" -> hermetic tokenizers; else -> HF hub/path.

    "id:4096" bounds the IdTokenizer to a 4096-vocab model so hashed or
    parsed prompt ids never exceed the served model's embedding table.
    """
    if name == "byte":
        return ByteTokenizer()
    if name == "id" or name.startswith("id:"):
        suffix = name.split(":", 1)[1] if ":" in name else ""
        if suffix and not suffix.isdigit():
            raise ValueError(
                f"bad id-tokenizer spec {name!r}: expected 'id' or "
                f"'id:<vocab_size>' (e.g. 'id:4096')")
        return IdTokenizer(int(suffix) if suffix else 32000)
    return HFTokenizer(name)
