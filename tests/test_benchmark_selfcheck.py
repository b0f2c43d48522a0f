"""``benchmark/tests``' judge and readers (``correct``, the GPT-2 serving
runner, the span, stall, trace and exchange readers, the traffic and the
counted operations) as tier-1 cases; ``tests/benchmark_selfcheck.py`` says how
and why."""

import benchmark_selfcheck as selfcheck

# 56 s alone on an idle machine (PR 38); beside five busy workers a
# subprocess takes 2-2.5 times what it takes alone; the limit is the
# subprocess's own
report, test_benchmark_test_passes = selfcheck.cases(
    ("test_correct", "test_exchange", "test_serve", "test_spans",
     "test_stalls", "test_trace", "test_traffic_and_flops"), 450)


PLANTED = '''
def rehearse():
    return {"attempted": 8, "token": 3}

def test_too_few():
    result = rehearse()
    assert result["attempted"] > 8

def test_wrong_token():
    result = rehearse()
    assert result["token"] == 4
    assert result["attempted"] > 8

def test_broken():
    result = rehearse()
    raise RuntimeError('assert result["attempted"] > 8')
'''


def test_only_a_starved_rehearsal_runs_again(tmp_path, monkeypatch):
    """The second chance is for the too-few-requests assertion of a named
    rehearsal, read off pytest's own report: any other failure of the
    same function, and the same assertion anywhere else, fails at once."""
    (tmp_path / "test_planted.py").write_text(PLANTED)
    ids = [f"{tmp_path}/test_planted.py::test_{name}"
           for name in ("too_few", "wrong_token", "broken")]
    cases, tail = selfcheck._pytest(ids, 120, tmp_path / "report.xml")
    assert len(cases) == 3 and None not in cases.values(), tail
    case = {name: (cls, name) for cls, name in cases}
    too_few, wrong, broken = (case[f"test_{name}"] for name in
                              ("too_few", "wrong_token", "broken"))
    assert not any(selfcheck.starved(c, cases[c]) for c in cases)
    monkeypatch.setattr(selfcheck, "REHEARSALS", set(cases))
    assert selfcheck.starved(too_few, cases[too_few])
    assert not selfcheck.starved(wrong, cases[wrong])
    assert not selfcheck.starved(broken, cases[broken])
