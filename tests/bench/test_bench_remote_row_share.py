"""remote_row_share reads the program's ``amg.lower.remote`` spans: the
share of the haloed operators' rows that the off-process product runs over,
from recorded spans and from a lowering on a 2×2 mesh of host devices;
nothing from a program without the span or a hierarchy without a halo."""
import os
import pathlib
import subprocess
import sys

import pytest

import spec

ROOT = pathlib.Path(__file__).parents[2]


def _reader():
    return spec.load_module(spec.BENCH_DIR / "metrics" / "remote_row_share.py",
                            "bench_metric_remote_row_share").read


def _span(i, name, **attrs):
    from repro.amg.spans import Span
    return Span(i, None, name, 0, 1, 1, attrs)


@pytest.fixture
def ring(monkeypatch):
    from repro.amg import spans

    def use(recorded):
        monkeypatch.setattr(spans, "recent", lambda: list(recorded))
    return use


def test_share_of_the_rows_the_remote_products_run_over(ring):
    """L0 compacted to a fifth of its rows, L1 kept whole, L2 (the coarsest,
    A alone) compacted to half."""
    ring([_span(1, "amg.lower.plan", level=0),
          _span(2, "amg.lower.remote", level=0, rows=900, remote_rows=180),
          _span(3, "amg.lower.remote", level=1, rows=80, remote_rows=80),
          _span(4, "amg.lower.remote", level=2, rows=20, remote_rows=10)])
    assert _reader()(None) == pytest.approx(27.0)


def test_a_hierarchy_without_a_halo_reads_nothing(ring):
    """One chip: every level's span counts no haloed operator."""
    ring([_span(1, "amg.lower.remote", level=0, rows=0, remote_rows=0),
          _span(2, "amg.lower.remote", level=1, rows=0, remote_rows=0)])
    assert _reader()(None) is None


def test_a_ring_without_the_span_reads_nothing(ring):
    """What the parent commit's program records: spans, none of them this
    one."""
    ring([_span(1, "amg.lower.plan", level=0),
          _span(2, "amg.lower.layout", level=0, layout="dia", nnz=9,
                dia_nnz=8),
          _span(3, "amg.pcg.step")])
    assert _reader()(None) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """An older program has no ``repro.amg.spans`` at all."""
    import repro.amg

    monkeypatch.delattr(repro.amg, "spans")
    monkeypatch.setitem(sys.modules, "repro.amg.spans", None)
    assert _reader()(None) is None


ON_FOUR = """
import sys
sys.path.insert(0, {bench!r})
import spec
from repro.amg import setup, spans
from repro.amg.dist_solve import DistHierarchy
from repro.amg.problems import laplace_3d

h = setup(laplace_3d(16), solver="rs")
for shape in ((1, 1), (2, 2)):
    spans.clear()
    dh = DistHierarchy.build(h, *shape)
    got = spec.load_module(spec.BENCH_DIR / "metrics" / "remote_row_share.py",
                           "m").read(None)
    ops = [op for dl in dh.levels for op in (dl.A, dl.P, dl.R)
           if op is not None and not op.halo_empty]
    want = (100.0 * sum(op.remote_rows for op in ops)
            / sum(op.rows_local for op in ops)) if ops else None
    print("SHARE", shape, got, want)
"""


def test_reads_a_lowering_on_four_devices():
    """A 16³ Laplacian lowered on one device reads nothing; on a 2×2 mesh
    it reads the haloed operators' compacted rows, below 100 %."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run(
        [sys.executable, "-c", ON_FOUR.format(bench=str(ROOT / "bench"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    lines = [ln.split(" ", 1)[1] for ln in out.stdout.splitlines()
             if ln.startswith("SHARE")]
    assert lines[0] == "(1, 1) None None"
    _, got, want = lines[1].rsplit(" ", 2)
    assert float(got) == pytest.approx(float(want))
    assert 0 < float(got) < 100
