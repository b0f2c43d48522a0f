"""``benchmark/tests``' untraced rehearsal and float8 control of
Xing4.0-29B-A4B as tier-1 cases; ``tests/benchmark_selfcheck.py`` says
how and why."""

import benchmark_selfcheck as selfcheck

# 79 s alone (PR 38); the limit is the subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_xing",), 600, only=selfcheck.XING_UNTRACED)
