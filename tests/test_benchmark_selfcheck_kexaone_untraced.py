"""``benchmark/tests``' untraced rehearsal, float8 control and planted
faults of K-EXAONE-236B-A23B as tier-1 cases;
``tests/benchmark_selfcheck.py`` says how and why."""

import benchmark_selfcheck as selfcheck

report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_kexaone",), 600, only=selfcheck.KEXAONE_UNTRACED)
