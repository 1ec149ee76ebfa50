"""Platform bring-up and compile-cache placement (``ddl_tpu.bringup``).

A run that wants a TPU and finds none must stop with the reason — never
fall back and publish CPU numbers under device names — and a CPU run
has to be asked for by name.
"""

import os
import re
import subprocess
import sys

import jax
import pytest

from ddl_tpu import bringup

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """bring-up places the process-wide compile cache; put it back."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_wants_tpu_finds_none_exits_with_reason(cache_config):
    with pytest.raises(SystemExit) as exc:
        bringup.bring_up(None)  # this suite runs on the CPU backend
    assert "needs a TPU" in str(exc.value.code)
    assert "by name" in str(exc.value.code)


def test_cpu_by_name_is_granted(cache_config):
    assert bringup.bring_up("cpu") == "cpu"


def test_unknown_request_rejected():
    with pytest.raises(ValueError, match="cpu|tpu"):
        bringup.bring_up("gpu")


def test_cache_placement(cache_config, monkeypatch, tmp_path):
    """Placed from outside when the variable is set (nothing set in
    code); otherwise a FIXED path under the checkout."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert bringup.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert bringup.configure_compile_cache() == os.path.join(
        REPO, ".jax_cache"
    )
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache"
    )


@pytest.mark.parametrize(
    "argv",
    [
        ["benchmarks/run.py", "--workload", "mistral-7b.tokens-4k"],
        ["chip_smoke.py"],
    ],
    ids=["benchmarks/run.py", "chip_smoke.py"],
)
def test_entry_script_fails_without_a_tpu(argv):
    """The whole entry point, as the driver runs it in a sandbox with no
    accelerator: non-zero exit, the reason on stderr, no result line."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, argv[0]), *argv[1:]],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout and '"metric' not in proc.stdout


def test_unknown_device_kind_has_no_peak():
    from benchmarks.lib import peaks

    assert peaks.peak_flops("TPU v5 lite") == 197e12
    assert peaks._peak("TPU v5 lite", 2) == 819e9  # HBM bytes/s
    for column in (1, 2, 3):  # FLOP/s, HBM bytes/s, ICI bytes/s a link
        with pytest.raises(LookupError, match="TPU v9"):
            peaks._peak("TPU v9 mega", column)


# The superseded bench estate (PR 28 deleted it): its names, spelled in
# pieces so that this file passes its own check.
_DELETED = re.compile(
    r"(?<![\w/.-])bench" r"\.py\b|bench" r"_smoke|DDL_" r"BENCH_|\bprobe"
    r"_(?:attn|ici|ingest|moe|opt|pipeline|pp|shm_put|shuffle|stream"
    r"|sustained|wire)\b|PERF_" r"NOTES|ADVICE" r"\.md"
    r"|\bmake [a-z]+-(?:bench|dryrun)\b|\bmake bench-"
)
#: Never searched: the benchmark's own files, the driver's record, the
#: history files, and what .gitignore lists.
_SKIP_DIRS = {
    ".git", ".scratch", ".jax_cache", ".pytest_cache", ".hypothesis",
    "__pycache__", "chiprun_out", "benchmarks",
}
_SKIP_FILES = {
    "CHANGES.md", "ISSUE.md", "REVIEW.md", "SURVEY.md", "PERF_LEDGER.jsonl",
}
#: Their history section (from a heading to the next named one, or to
#: the end) is not searched.
_HISTORY = {
    "ROADMAP.md": ("\n## Recent", None),
    "PERF.md": ("\n## 6. Findings", "\n## 7. "),
}


def _text_files():
    for root, dirs, files in os.walk(REPO):
        dirs[:] = sorted(d for d in dirs if d not in _SKIP_DIRS)
        for name in sorted(files):
            rel = os.path.relpath(os.path.join(root, name), REPO)
            if rel in _SKIP_FILES:
                continue
            if name == "Makefile" or name.endswith(
                (".py", ".md", ".toml", ".yml")
            ):
                yield rel


def _present(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        text = f.read()
    start, end = _HISTORY.get(rel, (None, None))
    if start and start in text:
        head, _, tail = text.partition(start)
        cut = tail.index(end) if end and end in tail else len(tail)
        # Blank lines in its place keep the reported line numbers true.
        text = head + "\n" * (start + tail[:cut]).count("\n") + tail[cut:]
    return text


def test_no_reference_to_deleted_entry_points():
    """Nothing outside the history files cites the deleted bench estate
    as an entry point, a gate or a source of numbers, and every file a
    ``Makefile`` recipe names is there."""
    hits = []
    for rel in _text_files():
        for n, line in enumerate(_present(rel).splitlines(), 1):
            if _DELETED.search(line):
                hits.append(f"{rel}:{n}: {line.strip()[:100]}")
    assert not hits, "\n".join(hits)

    with open(os.path.join(REPO, "Makefile"), encoding="utf-8") as f:
        recipes = [ln for ln in f.read().replace("\\\n", " ").splitlines()
                   if ln.startswith("\t")]
    assert recipes
    missing = []
    for recipe in recipes:
        words = recipe.split()
        paths = [w for w in words if "/" in w or w.endswith(".py")]
        paths += [
            w.replace(".", "/") for prev, w in zip(words, words[1:])
            if prev == "-m" and w.startswith(("tools.", "ddl_tpu."))
        ]
        for path in paths:
            full = os.path.join(REPO, path)
            if not (os.path.exists(full) or os.path.exists(full + ".py")):
                missing.append(f"{path} (in: {recipe.strip()})")
    assert not missing, missing
