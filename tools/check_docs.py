"""Docs checker: keep README.md and docs/*.md honest.

Four layers of checking (the first three are cheap and also run in tier-1
via tests/test_docs.py; the fourth runs in the CI docs job):

  1. LINK LINT — every relative markdown link target must exist on disk
     (anchors and external http(s)/mailto links are skipped).
  2. CODE BLOCKS — every ```python fenced block must be valid syntax
     (compile()); every `python -m <module>` referenced in a ```bash
     block must resolve to an importable module (the entry point exists).
  3. DOCSTRINGS — every public module-level function, class and public
     method in the user-facing surface (the src/repro/serve and
     src/repro/kernels packages, plus the public models/ modules:
     attention.py, transformer.py, api.py, dit.py) must carry a
     docstring (ast-based, no imports needed).
  4. --run — actually execute the cheap commands the docs promise: every
     command line in a bash block matching the RUNNABLE allowlist
     (pytest --collect-only, serving --smoke, gen_path_matrix --check)
     is run from the repo root with PYTHONPATH=src and must exit 0 — so
     the docs/paths.md support matrix failing --check fails the docs job.

Usage:
    PYTHONPATH=src python tools/check_docs.py          # lint only
    PYTHONPATH=src python tools/check_docs.py --run    # lint + execute
"""
from __future__ import annotations

import argparse
import glob
import os
import re
import subprocess
import sys

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
# documented commands run from the repo root with PYTHONPATH=src — mirror
# that here so `python -m <module>` references resolve the same way
for _p in (REPO, os.path.join(REPO, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
FENCE_RE = re.compile(r"```(\w*)\n(.*?)```", re.DOTALL)
MODULE_RE = re.compile(r"python -m ([\w.]+)")
# commands the docs claim are cheap enough to run anywhere (--check is the
# gen_path_matrix drift gate; --write intentionally NOT runnable)
RUNNABLE = ("--collect-only", "--smoke", "--check")


def doc_files() -> list[str]:
    return [os.path.join(REPO, "README.md")] + sorted(
        glob.glob(os.path.join(REPO, "docs", "*.md")))


def check_links(path: str) -> list[str]:
    errors = []
    text = open(path).read()
    base = os.path.dirname(path)
    for target in LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        rel = target.split("#", 1)[0]
        if rel and not os.path.exists(os.path.join(base, rel)):
            errors.append(f"{os.path.relpath(path, REPO)}: broken link "
                          f"-> {target}")
    return errors


def check_code_blocks(path: str) -> tuple[list[str], list[str]]:
    """Returns (errors, runnable bash command lines found in this file)."""
    errors, commands = [], []
    text = open(path).read()
    for lang, body in FENCE_RE.findall(text):
        if lang == "python":
            try:
                compile(body, f"<{os.path.basename(path)} python block>",
                        "exec")
            except SyntaxError as e:
                errors.append(f"{os.path.relpath(path, REPO)}: python "
                              f"block does not parse: {e}")
        elif lang in ("bash", "sh", "shell"):
            for line in body.splitlines():
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                for mod in MODULE_RE.findall(line):
                    import importlib.util
                    try:
                        found = importlib.util.find_spec(mod) is not None
                    except ModuleNotFoundError:
                        found = False
                    if not found:
                        errors.append(
                            f"{os.path.relpath(path, REPO)}: `python -m "
                            f"{mod}` names a module that does not import")
                if any(tok in line for tok in RUNNABLE):
                    commands.append(line)
    return errors, commands


# user-facing packages whose public surface must be documented
DOCSTRING_DIRS = (os.path.join("src", "repro", "serve"),
                  os.path.join("src", "repro", "kernels"),
                  os.path.join("src", "repro", "distributed"))
# individual public modules linted the same way (models/ has many internal
# modules; only the serving-facing surface is held to the docstring bar)
DOCSTRING_FILES = (os.path.join("src", "repro", "models", "attention.py"),
                   os.path.join("src", "repro", "models", "transformer.py"),
                   os.path.join("src", "repro", "models", "api.py"),
                   os.path.join("src", "repro", "models", "dit.py"),
                   os.path.join("src", "repro", "models", "mla.py"),
                   os.path.join("src", "repro", "models", "ssm.py"),
                   os.path.join("src", "repro", "models", "hybrid.py"))


def _docstring_targets() -> list[str]:
    paths = []
    for d in DOCSTRING_DIRS:
        paths += sorted(glob.glob(os.path.join(REPO, d, "*.py")))
    paths += [os.path.join(REPO, f) for f in DOCSTRING_FILES]
    return paths


def check_docstrings() -> list[str]:
    """Flag public functions/classes/methods in the DOCSTRING_DIRS
    packages and the DOCSTRING_FILES modules that carry no docstring
    (dunder and underscore-private names are exempt)."""
    import ast
    errors = []
    for path in _docstring_targets():
        rel = os.path.relpath(path, REPO)
        tree = ast.parse(open(path).read())
        defs = []
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.append((node.name, node))
            elif isinstance(node, ast.ClassDef):
                defs.append((node.name, node))
                defs += [(f"{node.name}.{sub.name}", sub)
                         for sub in node.body
                         if isinstance(sub, (ast.FunctionDef,
                                             ast.AsyncFunctionDef))]
        for qual, node in defs:
            if any(part.startswith("_") for part in qual.split(".")):
                continue
            if not ast.get_docstring(node):
                errors.append(f"{rel}: public `{qual}` missing a "
                              "docstring")
    return errors


def run_commands(commands: list[str]) -> list[str]:
    errors = []
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    for cmd in dict.fromkeys(commands):        # dedupe, keep order
        # docs write the PYTHONPATH prefix explicitly; the env covers it
        bare = re.sub(r"^PYTHONPATH=\S+\s+", "", cmd)
        print(f"$ {bare}", flush=True)
        try:
            proc = subprocess.run(bare, shell=True, cwd=REPO, env=env,
                                  capture_output=True, text=True,
                                  timeout=1200)
        except subprocess.TimeoutExpired:
            errors.append(f"documented command timed out (1200s): {bare}")
            continue
        if proc.returncode != 0:
            errors.append(f"documented command failed ({proc.returncode}): "
                          f"{bare}\n{proc.stdout[-2000:]}"
                          f"\n{proc.stderr[-2000:]}")
    return errors


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run", action="store_true",
                    help="also execute the documented cheap commands")
    args = ap.parse_args()
    errors, commands = [], []
    files = doc_files()
    missing = [f for f in files if not os.path.exists(f)]
    if missing:
        errors += [f"missing doc file: {m}" for m in missing]
        files = [f for f in files if os.path.exists(f)]
    for path in files:
        errors += check_links(path)
        e, c = check_code_blocks(path)
        errors += e
        commands += c
    errors += check_docstrings()
    if args.run:
        if not commands:
            errors.append("no runnable documented commands found — the "
                          "docs should promise at least a --collect-only "
                          "and a --smoke entry point")
        errors += run_commands(commands)
    print(f"checked {len(files)} docs, "
          f"{len(dict.fromkeys(commands))} runnable commands"
          f"{' (executed)' if args.run else ''}")
    for e in errors:
        print(f"ERROR: {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
