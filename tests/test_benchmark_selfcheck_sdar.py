"""``benchmark/tests``' cases of SDAR-30B-A3B-Chat's runner and readers as
tier-1 cases: the traced rehearsal, the layout of the teacher-forced
forward and the readers (the untraced rehearsal and the controls run from
``..._sdar_untraced.py``); ``tests/benchmark_selfcheck.py`` says how and
why."""

import benchmark_selfcheck as selfcheck

# the traced rehearsal is about a minute alone on a cold cache; the limit
# is the subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_sdar",), 600, without=selfcheck.SDAR_UNTRACED)
