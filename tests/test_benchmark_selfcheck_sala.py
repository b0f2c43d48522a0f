"""``benchmark/tests``' cases of MiniCPM-SALA's runner and reference as
tier-1 cases; ``tests/benchmark_selfcheck.py`` says how and why."""

import benchmark_selfcheck as selfcheck

# 48 s alone (PR 38); the limit is the subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_sala",), 450)
