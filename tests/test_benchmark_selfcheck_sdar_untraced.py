"""``benchmark/tests``' untraced rehearsal, float8 control and planted
faults of SDAR-30B-A3B-Chat as tier-1 cases;
``tests/benchmark_selfcheck.py`` says how and why."""

import benchmark_selfcheck as selfcheck

report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_sdar",), 600, only=selfcheck.SDAR_UNTRACED)
