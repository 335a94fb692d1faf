import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

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
