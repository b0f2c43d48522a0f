"""``benchmark/tests``' dropped scaling factor of Xing4.0-29B-A4B as a
tier-1 case; ``tests/benchmark_selfcheck.py`` says how and why."""

import benchmark_selfcheck as selfcheck

# 71 s alone (PR 38); the limit is the subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_xing",), 600, only=selfcheck.XING_SCALING)
