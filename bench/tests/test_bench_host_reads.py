"""The reader of ``decode.host_reads_per_step``: the mean ``reads`` of
the decode step's ``step.commit`` spans in the window, silent where the
program's commits do not count their reads, and 1.0 in a traced run at
smoke sizes."""
import pytest

import smoke
import run
from repro.obs.trace import Span

NAME = "decode.host_reads_per_step"
D0, D1 = ("worker", "d0"), ("worker", "d1")


def _ctx(spans):
    class _Tracer:
        pass

    tr = _Tracer()
    tr.spans = spans
    return run.LayerContext(requests=[{}] * 4, tracer=tr, calls={}, compiles={},
                            sizes={}, peak=None, flops=None, trace=None, t0=0.0, t1=100.0)


def _s(name, track, t0, t1, **attrs):
    return Span(name, track, t0, t1, attrs=attrs)


def test_host_reads_are_the_mean_reads_of_a_commit():
    spans = [_s("step.commit", D0, 1.0, 1.1, reads=1), _s("step.commit", D0, 2.0, 2.1, reads=3),
             _s("step.commit", D1, 3.0, 3.1, reads=2),
             _s("step.commit", D0, -1.0, -0.9, reads=9)]  # before the window
    assert run._reader(NAME).read(_ctx(spans)) == pytest.approx(2.0)


@pytest.mark.parametrize("spans", [
    [],
    [_s("tick.step", D0, 1.0, 2.0), _s("prefill", ("request", "r0"), 0, 1)],
    # commits of a program that does not count its reads
    [_s("step.commit", D0, 1.0, 1.1), _s("step.launch", D0, 0.5, 0.6)],
    None,
])
def test_a_program_without_counted_reads_gives_no_value(spans):
    ctx = _ctx(spans or [])
    if spans is None:
        ctx.tracer = None
    assert run._reader(NAME).read(ctx) is None


def test_a_traced_run_reads_one_read_per_step():
    res = run.Run(smoke.spec("yi"), 2**31 + 11, 1.5, True, require_tpu=False).go()
    assert res["correct"] is True
    assert res["metrics"][NAME]["value"] == pytest.approx(1.0)
