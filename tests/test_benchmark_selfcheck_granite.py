"""``benchmark/tests``' cases of granite-4.0-h-small's runner and readers
as tier-1 cases: the traced rehearsal and the readers (the untraced
rehearsal and the controls run from ``..._granite_untraced.py``);
``tests/benchmark_selfcheck.py`` says how and why."""

import benchmark_selfcheck as selfcheck

# the traced rehearsal is about a minute alone on a cold cache; the limit
# is the subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_granite",), 600, without=selfcheck.GRANITE_UNTRACED)
