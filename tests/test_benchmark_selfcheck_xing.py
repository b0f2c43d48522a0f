"""``benchmark/tests``' cases of Xing4.0-29B-A4B's runner and reference
as tier-1 cases: the traced rehearsal and the readers (four more long
functions run from ``..._xing_untraced.py``, ``..._xing_scaling.py`` and
``..._xing_faults.py``); ``tests/benchmark_selfcheck.py`` says how and
why."""

import benchmark_selfcheck as selfcheck

# its one long function, the traced rehearsal, is 83 s alone on a cold
# cache (PR 38) and two to two and a half times that beside five busy
# workers; the limit is the subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_serve_xing",), 600,
    without=(selfcheck.XING_UNTRACED + selfcheck.XING_SCALING
             + selfcheck.XING_FAULTS))
