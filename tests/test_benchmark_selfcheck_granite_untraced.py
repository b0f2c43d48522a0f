"""``benchmark/tests``' untraced rehearsal, float8 control and planted
faults of granite-4.0-h-small as tier-1 cases;
``tests/benchmark_selfcheck.py`` says how and why."""

import benchmark_selfcheck as selfcheck

report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_granite",), 600, only=selfcheck.GRANITE_UNTRACED)
