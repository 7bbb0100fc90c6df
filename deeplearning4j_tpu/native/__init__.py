"""Native (C++) runtime components.

The reference's runtime around the compute path is native (ND4J C++ ops,
DataVec's JavaCV-backed readers); the TPU build keeps XLA as the compute
path and implements its IO hot paths in C++ too. Modules here are built
with `g++` on first use (no pybind11 in the image — plain `extern "C"` +
ctypes) and every caller has a pure-Python fallback, so the package works
on machines without a toolchain — saying so once, by warning, when a
build or load fails.

Current components:
- `fastcsv` — numeric CSV -> float32 matrix parser
  (`parse_numeric_csv`), used by `datasets/records.py`'s
  `CSVRecordReader.numeric_matrix`. ~4x the csv-module path on a
  100k x 10 file (PERF.md §7).
- `fastvocab` — tokenizer + vocab counter + corpus encoder
  (`build_vocab_corpus`), used by `nlp/word2vec.py`'s fit path; replaces
  the Python dict-count + per-token index lookups (PERF.md §5's 1-2 s
  of host string handling at 2M words). Exactness guards: falls back to
  the Python path whenever byte-level processing could diverge from
  Python string semantics (non-ASCII with the preprocessor, tokens
  containing separators, non-default tokenizers).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIBS: dict = {}  # name -> CDLL | None (None = build failed, don't retry)


def _build_and_load(name: str, configure) -> Optional[ctypes.CDLL]:
    src = os.path.join(_HERE, f"{name}.cpp")
    so = os.path.join(_HERE, f"_{name}.so")
    try:
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(src)):
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
                 "-o", so + ".tmp", src],
                check=True, capture_output=True, timeout=120)
            os.replace(so + ".tmp", so)
        lib = ctypes.CDLL(so)
        configure(lib)
        return lib
    except (OSError, subprocess.SubprocessError, AttributeError) as e:
        detail = getattr(e, "stderr", None)
        warnings.warn(
            f"native component {name!r} could not be built or loaded "
            f"({type(e).__name__}: {e}"
            + (f"; g++ said: {detail.decode(errors='replace')[-400:]}"
               if detail else "")
            + "); its callers use their pure-Python path, which is slower")
        return None


def _configure_fastcsv(lib):
    lib.csv_dims.restype = ctypes.c_long
    lib.csv_dims.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_long,
        ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long)]
    lib.csv_parse.restype = ctypes.c_long
    lib.csv_parse.argtypes = [
        ctypes.c_char_p, ctypes.c_char, ctypes.c_long,
        ctypes.POINTER(ctypes.c_float), ctypes.c_long, ctypes.c_long]


def _configure_fastvocab(lib):
    L = ctypes.c_long
    lib.vocab_build.restype = L
    lib.vocab_build.argtypes = [ctypes.c_char_p, L, ctypes.c_int,
                                ctypes.c_int, ctypes.c_double]
    lib.vocab_stats.restype = L
    lib.vocab_stats.argtypes = [L] + [ctypes.POINTER(L)] * 5
    lib.vocab_dump.restype = L
    lib.vocab_dump.argtypes = [L, ctypes.c_char_p,
                               ctypes.POINTER(ctypes.c_double)]
    lib.vocab_encode.restype = L
    lib.vocab_encode.argtypes = [L, ctypes.POINTER(ctypes.c_int),
                                 ctypes.POINTER(ctypes.c_longlong)]
    lib.vocab_free.restype = None
    lib.vocab_free.argtypes = [L]


_CONFIGURE = {"fastcsv": _configure_fastcsv, "fastvocab": _configure_fastvocab}


def _lib(name: str = "fastcsv") -> Optional[ctypes.CDLL]:
    if name not in _LIBS:
        with _LOCK:
            if name not in _LIBS:
                _LIBS[name] = _build_and_load(name, _CONFIGURE[name])
    return _LIBS[name]


def native_available() -> bool:
    return _lib("fastcsv") is not None


def parse_numeric_csv(path: str, delimiter: str = ",",
                      skip: int = 0) -> Optional[np.ndarray]:
    """Parse an all-numeric CSV into a float32 [rows, cols] matrix with the
    native parser. Returns None when the native library is unavailable OR
    the file isn't uniformly numeric (callers fall back to the Python
    reader — same result, slower)."""
    lib = _lib()
    if lib is None or len(delimiter.encode()) != 1:
        return None
    rows = ctypes.c_long()
    cols = ctypes.c_long()
    p = os.fsencode(path)
    d = delimiter.encode()
    if lib.csv_dims(p, d, skip, ctypes.byref(rows), ctypes.byref(cols)) != 0:
        return None
    if rows.value == 0 or cols.value == 0:
        return np.zeros((rows.value, cols.value), np.float32)
    out = np.empty((rows.value, cols.value), np.float32)
    rc = lib.csv_parse(p, d, skip,
                       out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                       rows.value, cols.value)
    return out if rc == 0 else None


def build_vocab_corpus(sentences, min_word_frequency: float = 1.0,
                       tokenizer_factory=None):
    """Native tokenize + vocab count + encode for the embedding trainers.

    Returns (words, counts, seqs) — vocab words in finalize_vocab order,
    float counts, and each sentence as an int32 index array with OOV
    dropped — or None when the fast path can't GUARANTEE Python-identical
    results (caller falls back to `VocabConstructor` — same output,
    slower). `sentences` must be a sequence (materialized), either all raw
    strings or all pre-split token lists.
    """
    from deeplearning4j_tpu.nlp.tokenization import (
        CommonPreprocessor, TokenizerFactory,
    )

    lib = _lib("fastvocab")
    if lib is None or not isinstance(sentences, (list, tuple)):
        return None
    # Tokenizer guard: only the default whitespace tokenizer, bare or with
    # CommonPreprocessor, has a native equivalent.
    mode = 0
    if tokenizer_factory is not None:
        if type(tokenizer_factory) is not TokenizerFactory:
            return None
        pre = tokenizer_factory.preprocessor
        if pre is None:
            pass
        elif type(pre) is CommonPreprocessor:
            mode = 1
        else:
            return None

    if all(isinstance(s, str) for s in sentences):
        raw = True
        try:
            buf = "\n".join(sentences).encode("utf-8")
        except Exception:
            return None
        # Python str.split also splits on UNICODE whitespace; restrict the
        # raw path to ASCII so byte-level splitting can't diverge.
        strict_ascii = 1
        n_expected_seqs = None  # embedded '\n' changes it; checked below
    elif all(isinstance(s, (list, tuple)) for s in sentences):
        raw = False
        try:
            buf = "\n".join(" ".join(s) for s in sentences).encode("utf-8")
        except Exception:
            return None
        # Pre-split lists are used as-is by tokenize_corpus (no
        # preprocessor), so mode drops to 0; UTF-8 byte order == code-point
        # order keeps the sort tie-break identical, so non-ASCII is fine.
        mode = 0
        strict_ascii = 0
        n_expected_seqs = len(sentences)
    else:
        return None  # mixed corpus: per-line mode switching not supported

    h = lib.vocab_build(buf, len(buf), mode, strict_ascii,
                        float(min_word_frequency))
    if h < 0:
        return None
    try:
        n_words = ctypes.c_long()
        words_bytes = ctypes.c_long()
        n_seqs = ctypes.c_long()
        n_idx = ctypes.c_long()
        n_raw = ctypes.c_long()
        if lib.vocab_stats(h, ctypes.byref(n_words), ctypes.byref(words_bytes),
                           ctypes.byref(n_seqs), ctypes.byref(n_idx),
                           ctypes.byref(n_raw)) != 0:
            return None
        if raw:
            # A sentence containing '\n' splits differently: reject.
            if n_seqs.value != len(sentences):
                return None
        else:
            # A token containing whitespace splits into more tokens than
            # Python saw: reject (exactness guard).
            if n_seqs.value != n_expected_seqs:
                return None
            if n_raw.value != sum(len(s) for s in sentences):
                return None
        wb = ctypes.create_string_buffer(max(1, words_bytes.value))
        counts = np.zeros((n_words.value,), np.float64)
        if lib.vocab_dump(
                h, wb, counts.ctypes.data_as(
                    ctypes.POINTER(ctypes.c_double))) != 0:
            return None
        words = (wb.raw[:words_bytes.value].decode("utf-8").split("\n")[:-1]
                 if words_bytes.value else [])
        ids = np.zeros((max(1, n_idx.value),), np.int32)
        offs = np.zeros((n_seqs.value + 1,), np.int64)
        if lib.vocab_encode(
                h, ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
                offs.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong))) != 0:
            return None
        ids = ids[: n_idx.value]
        seqs = [ids[offs[i]:offs[i + 1]] for i in range(n_seqs.value)]
        return words, counts, seqs
    finally:
        lib.vocab_free(h)
