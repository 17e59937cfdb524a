"""The text-classification cell's load generator: product titles in posts
of ``batch`` to /batch/queries.json, closed loop. Standard library and
numpy only, as `loadgen.py` is, whose clock, post and result shapes it
keeps: run as a script it reads one JSON plan on standard input and
prints one JSON result.

A title: its length in tokens log-normal (``title_tokens``: median,
sigma, clipped to [min, max]), each token a word drawn Zipf over the
``vocabulary`` (:func:`vocabulary`, spelled from the seed), written in
lower, Capitalised or UPPER case and joined by spaces and punctuation,
so that the server's tokenizer has case to fold and separators to drop.
Lower-cased, its tokens are the drawn words (a test holds this).
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import time

import numpy as np

from loadgen import REQUEST_TIMEOUT_S, _post, _run_threads, zipf_weights

#: word separators and their shares; none holds a token character
SEPARATORS = (" ", ", ", " - ", " / ", " & ")
SEPARATOR_SHARES = (0.85, 0.05, 0.04, 0.03, 0.03)
#: shares of words written lower, Capitalised and UPPER
CASE_SHARES = (0.6, 0.3, 0.1)
#: shares of the vocabulary spelled with digits among the letters
#: (model numbers, sizes) and of words with a possessive "'s"
DIGIT_SHARE, POSSESSIVE_SHARE = 0.05, 0.01


def vocabulary(seed: int, size: int) -> list[str]:
    """``size`` distinct lowercase words spelled from the seed, in the
    order of their Zipf rank: 2-10 letters, some with digits among them,
    some possessive."""
    rng = np.random.default_rng([seed, 0x7E47])
    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789", np.uint8)
    words: dict[str, None] = {}
    while len(words) < size:
        n = 2 * (size - len(words)) + 64
        lengths = rng.integers(2, 11, n)
        digits = rng.random(n) < DIGIT_SHARE
        alphabet = np.where(np.repeat(digits, lengths), 36, 26)
        chars = letters[(rng.random(lengths.sum()) * alphabet).astype(np.int64)]
        text = chars.tobytes().decode("ascii")
        ends = np.cumsum(lengths)
        possessive = rng.random(n) < POSSESSIVE_SHARE
        for end, length, pos in zip(ends.tolist(), lengths.tolist(), possessive.tolist()):
            word = text[end - length:end] + ("'s" if pos else "")
            words.setdefault(word)
            if len(words) == size:
                break
    return list(words)


def title_lengths(rng, n: int, spec: dict) -> np.ndarray:
    raw = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def draw_titles(rng, words: list[str], cdf: np.ndarray, n: int, spec: dict):
    """``(titles, token lists)`` of ``n`` titles from one generator."""
    lengths = title_lengths(rng, n, spec)
    total = int(lengths.sum())
    ranks = np.minimum(np.searchsorted(cdf, rng.random(total), side="right"), len(words) - 1)
    cases = np.searchsorted(np.cumsum(CASE_SHARES), rng.random(total), side="right")
    seps = np.searchsorted(np.cumsum(SEPARATOR_SHARES), rng.random(total), side="right")
    titles, drawn, at = [], [], 0
    for length in lengths.tolist():
        parts, toks = [], []
        for i in range(at, at + length):
            word = words[ranks[i]]
            toks.append(word)
            case = cases[i]
            if case == 1:
                word = word.capitalize()
            elif case == 2:
                word = word.upper()
            if parts:
                parts.append(SEPARATORS[min(seps[i], len(SEPARATORS) - 1)])
            parts.append(word)
        titles.append("".join(parts))
        drawn.append(toks)
        at += length
    return titles, drawn


def _count_ok(data: bytes, expect: int, num: int) -> int:
    """Queries of a batch reply answered with ``num`` classes."""
    try:
        slots = json.loads(data)
        return sum(
            1 for s in slots[:expect]
            if s.get("status") == 200 and len(s["prediction"]["scores"]) == num
        )
    except (ValueError, KeyError, TypeError, AttributeError):
        return 0


def run_closed(plan: dict) -> dict:
    host, port = plan["host"], plan["port"]
    t_window, t_end = plan["t_window"], plan["t_end"]
    batch, num = plan["batch"], plan["num"]
    words = vocabulary(plan["seed"], plan["vocabulary"])
    cdf = np.cumsum(zipf_weights(len(words), plan["word_zipf_exponent"]))
    results = [[] for _ in range(plan["clients"])]
    kept = [[] for _ in range(plan["clients"])]

    def client(w: int) -> None:
        rng = np.random.default_rng([plan["seed"], plan["proc"], w])
        conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
        seen = 0
        while time.monotonic() < t_end:
            titles, _ = draw_titles(rng, words, cdf, batch, plan["title_tokens"])
            body = json.dumps([{"text": t, "num": num} for t in titles]).encode()
            try:
                status, data = _post(conn, "/batch/queries.json", body)
                ok = _count_ok(data, batch, num) if status == 200 else 0
                answered = True
            except (OSError, http.client.HTTPException):
                ok, answered, data = 0, False, b""
                conn.close()
                conn = http.client.HTTPConnection(host, port, timeout=REQUEST_TIMEOUT_S)
            done = time.monotonic()
            results[w].append((done, ok, batch - ok, answered))
            if answered and done >= t_window:
                # reservoir of this client's replies inside the window
                seen += 1
                entry = (titles, data.decode("utf-8", "replace"))
                if len(kept[w]) < plan["keep"]:
                    kept[w].append(entry)
                elif rng.random() < plan["keep"] / seen:
                    kept[w][int(rng.integers(plan["keep"]))] = entry
        conn.close()

    _run_threads(client, plan["clients"])
    return {
        "posts": [r for rs in results for r in rs],
        "kept": [k for ks in kept for k in ks],
    }


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    if plan.get("cores"):
        os.sched_setaffinity(0, plan["cores"])
    json.dump(run_closed(plan), sys.stdout)
    sys.stdout.write("\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
