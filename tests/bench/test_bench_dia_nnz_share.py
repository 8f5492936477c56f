"""dia_nnz_share reads the program's ``amg.lower.layout`` spans: the DIA
share of the hierarchy's ``A`` nonzeros from a synthetic ring, and nothing
from a ring without such spans or from a program without spans."""
import sys

import pytest

import spec


def _reader():
    return spec.load_module(spec.BENCH_DIR / "metrics" / "dia_nnz_share.py",
                            "bench_metric_dia_nnz_share").read


def _span(i, name, **attrs):
    from repro.amg.spans import Span
    return Span(i, None, name, 0, 1, 1, attrs)


@pytest.fixture
def ring(monkeypatch):
    from repro.amg import spans

    def use(recorded):
        monkeypatch.setattr(spans, "recent", lambda: list(recorded))
    return use


def test_share_of_the_nonzeros_lowered_to_dia(ring):
    ring([_span(1, "amg.lower.plan", level=0),
          _span(2, "amg.lower.layout", level=0, layout="dia", diagonals=27,
                nnz=900, dia_nnz=800),
          _span(3, "amg.lower.layout", level=1, layout="ell", diagonals=0,
                nnz=80, dia_nnz=0),
          _span(4, "amg.lower.layout", level=2, layout="bcsr", diagonals=0,
                nnz=20, dia_nnz=0)])
    assert _reader()(None) == pytest.approx(80.0)


def test_no_dia_level_reads_zero(ring):
    ring([_span(1, "amg.lower.layout", level=0, layout="ell", diagonals=0,
                nnz=50, dia_nnz=0)])
    assert _reader()(None) == 0.0


def test_an_empty_ring_reads_nothing(ring):
    ring([])
    assert _reader()(None) is None


def test_a_ring_without_layout_spans_reads_nothing(ring):
    """What the parent commit's program records: spans, none of them a
    layout."""
    ring([_span(1, "amg.lower.plan", level=0),
          _span(2, "amg.pcg.step")])
    assert _reader()(None) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """An older program has no ``repro.amg.spans`` at all."""
    import repro.amg

    monkeypatch.delattr(repro.amg, "spans")
    monkeypatch.setitem(sys.modules, "repro.amg.spans", None)
    assert _reader()(None) is None
