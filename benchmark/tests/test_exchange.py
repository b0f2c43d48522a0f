"""The exchange's readers on event lists as the chip's trace names them
(the names are cut from a traced step of ``bertl-train-dp4``, PR 40)."""

import importlib.util
import os

import pytest

from benchmark import exchange, trace

HERE = os.path.dirname(os.path.abspath(__file__))

START = ("%async-collective-start.3 = (bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)}"
         ", bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}) fusion("
         "bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} %fusion.71), kind=kCustom, "
         "calls=%async_collective_fusion.712")
HOST = ("%fusion.80 = (f32[1024,16,64]{0,2,1:T(8,128)S(1)}, bf16[1024,4096]"
        "{1,0:T(8,128)(2,1)S(1)}, s32[2]{0:S(4)}) fusion(f32[1024,16,64]"
        "{0,2,1:T(8,128)} %get-tuple-element.540), kind=kLoop, "
        "calls=%fused_computation.415")
DONE = ("%async-collective-done.3 = bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} "
        "fusion(bf16[1024,4096]{1,0:T(8,128)(2,1)S(1)} %get-tuple-element.5"
        "), kind=kCustom, calls=%async_collective_fusion.715")
COPY = ("%copy-start.9 = (bf16[1024,4096]{1,0:T(8,128)(2,1)}, bf16[1024,4096]"
        "{1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(bf16[1024,4096]"
        "{1,0:T(8,128)(2,1)S(1)} %async-collective-done.3)")
SYNC = ("%all-reduce.376 = (bf16[16,64]{1,0:T(8,128)(2,1)}, bf16[1024]"
        "{0:T(1024)(128)(2,1)}) all-reduce(bf16[16,64]{1,0:T(8,128)(2,1)} "
        "%fusion.5, bf16[1024]{0:T(1024)(128)(2,1)} %fusion.6), "
        "channel_id=60, replica_groups=[1,4]<=[4], to_apply=%add.clone")
ADAM = ("%fusion.91 = f32[1024]{0:T(1024)} fusion(bf16[1024]{0:T(1024)(128)"
        "(2,1)} %all-reduce.376), kind=kLoop, calls=%fused_computation.9")
KERNEL = ('%flash_dq.1 = bf16[16,16,512,64]{3,2,1,0} custom-call(), '
          'custom_call_target="tpu_custom_call"')

# one chain (2 us start, a 60 us host, an 80 us done), the copy of what it
# reduced, a 15 us synchronous tuple and the update that reads it
ASYNC_STEP = [(KERNEL, 0, 100_000), (START, 100_000, 2_000),
              (HOST, 102_000, 60_000), (DONE, 162_000, 80_000),
              (COPY, 242_000, 1_000), (SYNC, 243_000, 15_000),
              (ADAM, 258_000, 5_000)]
# the same bytes as one synchronous all-reduce, as the parent runs it
SYNC_STEP = [(KERNEL, 0, 100_000), (HOST, 100_000, 60_000),
             (SYNC, 160_000, 146_000), (ADAM, 306_000, 5_000)]


@pytest.mark.parametrize("name,kind", [
    (START, "start"), (DONE, "done"), (SYNC, "sync"), (HOST, None),
    (COPY, None), (ADAM, None), (KERNEL, None),
    ("%all-reduce-start.2 = bf16[8]{0} all-reduce-start(bf16[8]{0} %p)",
     "start"),
    ("%all-reduce-done.2 = bf16[8]{0} all-reduce-done(%all-reduce-start.2)",
     "done"),
    ("%all-gather.4 = bf16[8]{0} all-gather(bf16[2]{0} %p)", "sync")])
def test_an_event_is_told_by_its_own_instruction(name, kind):
    assert exchange.kind(name) == kind


def test_a_chain_is_open_from_its_start_to_its_done():
    assert exchange.open_seconds(ASYNC_STEP) == pytest.approx(
        (2 + 60 + 80 + 15) * 1e-6)
    assert exchange.exposed_seconds(ASYNC_STEP) == pytest.approx(
        (2 + 80 + 15) * 1e-6)
    # the accepted matcher sees the tuple and the update that names it,
    # and nothing of the chain
    assert trace.collective_seconds(ASYNC_STEP)[0] == pytest.approx(
        (15 + 5) * 1e-6)


def test_a_synchronous_program_reads_as_the_accepted_reader_reads_it():
    events = [e for e in SYNC_STEP if e[0] is not ADAM]
    total, exposed = trace.collective_seconds(events)
    assert exchange.open_seconds(events) == pytest.approx(total)
    assert exchange.exposed_seconds(events) == pytest.approx(exposed)
    assert exposed == pytest.approx(146e-6)


def test_a_slice_that_ends_inside_a_chain_closes_it():
    events = ASYNC_STEP[:3]
    assert exchange.open_seconds(events) == pytest.approx(62e-6)
    assert exchange.open_seconds([]) == 0.0


def _reader(name):
    path = os.path.join(HERE, "..", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("events,exposed_ms,share", [
    (ASYNC_STEP, 0.097, 100 * (1 - 97 / 157)),
    (SYNC_STEP, 0.146, 0.0)], ids=["asynchronous", "synchronous"])
def test_the_two_readers(events, exposed_ms, share):
    summary = {"trace": {"events": events}, "chips": 4, "trace_steps": 1}
    assert _reader("exchange_exposed_ms_step.dp4")(summary) == (
        pytest.approx(exposed_ms))
    assert _reader("exchange_overlap_share.dp4")(summary) == (
        pytest.approx(share))


@pytest.mark.parametrize("summary", [
    {"trace": {}, "chips": 4, "trace_steps": 3},
    {"trace": {"events": ASYNC_STEP}, "chips": 1, "trace_steps": 3},
    {"trace": {"events": [(KERNEL, 0, 10)]}, "chips": 4}],
    ids=["untraced", "one-chip", "a-serving-cell"])
def test_nothing_to_read_is_none(summary):
    assert _reader("exchange_exposed_ms_step.dp4")(summary) is None
    assert _reader("exchange_overlap_share.dp4")(summary) is None
