#!/usr/bin/env python
"""Execute the fenced `python` blocks of markdown docs so documented
examples can't bit-rot.

    PYTHONPATH=src python tools/check_docs.py [FILE.md ...]

Defaults to the files whose snippets are the repo's executable
contract: ROADMAP.md and docs/ARCHITECTURE.md (the CI `docs` job runs
exactly these; docs/fhe_gpt2_walkthrough.md is narrative — its
fragments reference the example's namespace and are covered by running
`examples/fhe_gpt2.py` itself).

All blocks within one file share a namespace: they are concatenated in
order into one script and executed in a subprocess, so later snippets
can build on earlier ones.  This parent never imports JAX: a chip
belongs to one process, and each snippet's child may need it.  Only
fences whose info string is exactly
``python`` run; ```text fences and annotated fences are
documentation-only.
"""
from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

DEFAULT_FILES = ["ROADMAP.md", os.path.join("docs", "ARCHITECTURE.md")]


def extract_python_blocks(path: str) -> list:
    blocks: list = []
    cur: list = []
    in_block = False
    with open(path, encoding="utf-8") as f:
        for line in f:
            stripped = line.strip()
            if in_block:
                if stripped == "```":
                    in_block = False
                    blocks.append("".join(cur))
                    cur = []
                else:
                    cur.append(line)
            elif stripped == "```python":
                in_block = True
    assert not in_block, f"{path}: unterminated ```python fence"
    return blocks


def run_file_snippets(path: str) -> bool:
    blocks = extract_python_blocks(path)
    if not blocks:
        print(f"[docs] {path}: no python blocks, skipped")
        return True
    script = "\n\n".join(blocks)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    with tempfile.NamedTemporaryFile(
            "w", suffix=".py", delete=False, encoding="utf-8") as tf:
        tf.write(script)
        tmp = tf.name
    try:
        t0 = time.time()
        proc = subprocess.run([sys.executable, tmp], env=env)
        dt = time.time() - t0
        ok = proc.returncode == 0
        print(f"[docs] {path}: {len(blocks)} block(s) "
              f"{'ok' if ok else 'FAILED'} in {dt:.1f}s")
        return ok
    finally:
        os.unlink(tmp)


def main(argv=None) -> int:
    files = list(argv) if argv else DEFAULT_FILES
    bad = [f for f in files if not run_file_snippets(f)]
    if bad:
        print(f"[docs] FAILED: {bad}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
