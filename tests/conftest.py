"""Hypothesis draws the same examples in every run, with no example
database, so the suite passes or fails, and counts, alike everywhere."""
try:
    from hypothesis import settings
except ImportError:          # pragma: no cover - CI installs hypothesis
    pass
else:
    settings.register_profile("repro", derandomize=True, database=None)
    settings.load_profile("repro")
