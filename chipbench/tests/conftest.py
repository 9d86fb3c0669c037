"""Fixtures of the benchmark's tests.

Loading a scheduler reader wraps ``trace_reduce.reduce_file`` so that it
also reads the program's spans (``spans.keep_program_spans``): the
benchmark's run needs that, and it would outlast the test that loaded the
reader.  Each test here starts and ends with the plain reduction."""
import pytest

from chipbench import spans, trace_reduce


@pytest.fixture(autouse=True)
def plain_reduction(monkeypatch):
    monkeypatch.setattr(trace_reduce, "reduce_file", spans.plain_reduce_file())
