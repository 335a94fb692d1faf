import os
import pathlib
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

# Tier-1 selection tests assert the *static* dispatch heuristics; a warm
# developer tuning cache (.autotune.json in the checkout) must not flip them.
# Point the autotune cache at a fresh per-run path unless the environment
# already pins one; tuning tests repoint it again via monkeypatch.
os.environ.setdefault(
    "REPRO_AUTOTUNE_CACHE",
    os.path.join(tempfile.gettempdir(),
                 f"repro_test_autotune_{os.getpid()}.json"))

# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single device; only launch/dryrun.py forces 512 host devices.

# The property-test modules import hypothesis at module scope; without it
# installed they are 7 hard collection errors that abort the whole run.
# Degrade to a collect-time skip instead: detect the importers by source
# scan (no hardcoded list to drift) and ignore them, reporting which.
try:
    import hypothesis  # noqa: F401
    _NO_HYPOTHESIS: list[str] = []
except ModuleNotFoundError:
    _HERE = pathlib.Path(__file__).resolve().parent
    _NO_HYPOTHESIS = sorted(
        p.name for p in _HERE.glob("test_*.py")
        if "from hypothesis" in p.read_text() or "import hypothesis" in p.read_text()
    )
    collect_ignore = list(_NO_HYPOTHESIS)


def pytest_report_header(config):
    if _NO_HYPOTHESIS:
        return (f"hypothesis not installed: skipping {len(_NO_HYPOTHESIS)} "
                f"property-test modules ({', '.join(_NO_HYPOTHESIS)})")
    return None
