"""Shared pytest config: the known-failures quarantine.

``tests/known_failures.txt`` lists test node ids that fail for known,
environment-level reasons (tracked in the file's comments).  They are
*quarantined* — marked ``xfail(strict=False)`` so the tier-1 gate stays
green without deleting the tests — and un-quarantine automatically the
moment they start passing (xpass is not an error; just remove the line).

Set ``REPRO_NO_QUARANTINE=1`` to run the suite without the marker (e.g. to
regenerate the list).
"""
from __future__ import annotations

import os
import pathlib

import pytest

_LIST = pathlib.Path(__file__).parent / "known_failures.txt"


def _load_known_failures() -> set[str]:
    if os.environ.get("REPRO_NO_QUARANTINE") or not _LIST.exists():
        return set()
    out = set()
    for line in _LIST.read_text().splitlines():
        line = line.strip()
        if line and not line.startswith("#"):
            out.add(line)
    return out


_KNOWN = _load_known_failures()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.nodeid in _KNOWN:
            item.add_marker(pytest.mark.xfail(
                reason="quarantined: see tests/known_failures.txt",
                strict=False))


@pytest.fixture(autouse=True, scope="class")
def _bound_jax_compile_cache():
    """Flush jax's in-process caches at each class (or module) boundary.

    A full tier-1 run compiles thousands of distinct programs into one
    process; past a few hundred, XLA:CPU's compiler can segfault on an
    otherwise-fine compile (observed deterministically at ~470 tests in —
    the same test passes in isolation or any shorter prefix; one module,
    ``test_graph_advance.py``, crossed that line alone).  Clearing between
    test classes keeps the live compiled-program population bounded;
    within a class, tests still share traces, so the re-trace cost is one
    warmup per class, not per test.
    """
    yield
    import jax
    jax.clear_caches()
