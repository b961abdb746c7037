"""``ssd_kernel_pct``: the Mosaic kernels' share of the SSD scan's device
time, on a small synthetic HLO module and trace."""
import pytest

from bench_fixtures import import_harness
from test_bench_scopes import _reading

harness, spec = import_harness()
import hlo  # noqa: E402

SSD = "jit(train_step)/jvp()/while/body/closed_call/ssm/ssd"
SSD_BWD = ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
           "checkpoint/ssm/ssd")


def _module(kernel: bool) -> str:
    """An ``ssd`` fusion, an ``ssd`` op that is a Mosaic kernel or a plain
    fusion, a backward ``ssd`` kernel or fusion, and an op outside the
    scan."""
    def op(name, op_name, mosaic):
        call = ('custom-call(%p), custom_call_target="tpu_custom_call"'
                if mosaic else "fusion(%p), kind=kLoop, calls=%f")
        return (f'  %{name} = f32[8]{{0}} {call}, '
                f'metadata={{op_name="{op_name}" source_file="m.py" '
                f'source_line=3}}')
    return "\n".join([
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        op("fusion.1", SSD + "/mul", False),
        op("scan.2", SSD + "/pallas_call", kernel),
        op("scan.3", SSD_BWD + "/pallas_call", kernel),
        op("fusion.4", "jit(train_step)/jvp()/while/body/closed_call/ssm/mul",
           False),
        "  ROOT %t = f32[8]{0} copy(%fusion.4)",
        "}"])


def _events(cores):
    """Per core (op, start, end) in ns: the ssd fusion 100, the forward
    scan 300, the backward scan 600, the other op 1000."""
    evs = [("fusion.1", 0.0, 100.0), ("scan.2", 100.0, 400.0),
           ("scan.3", 400.0, 1000.0), ("fusion.4", 1000.0, 2000.0)]
    return {"devices": {f"/device:TPU:{i}": list(evs) for i in range(cores)},
            "host": [("window", 0.0, 2000.0)]}


@pytest.fixture(scope="module")
def reader():
    return spec.load_cell("mamba2-130m.train.s2048").readers["ssd_kernel_pct"]


@pytest.mark.parametrize("cores", [1, 4])
def test_share_of_kernels_in_ssd_time(reader, cores):
    text = _module(kernel=True)
    r = _reading(_events(cores), hlo.Program(text).op_classes(), text)
    assert reader.read(r) == pytest.approx(90.0)
    # the kernels are charged to the scan: 1,000 of its 1,000 ns
    ssd_ms = spec.load_cell("mamba2-130m.train.s2048").readers["ssd_ms"]
    assert ssd_ms.read(r) == pytest.approx(1e-3)


def test_reads_zero_without_kernel(reader):
    text = _module(kernel=False)
    r = _reading(_events(1), hlo.Program(text).op_classes(), text)
    assert reader.read(r) == 0.0


def test_silent_without_ssd_scope(reader):
    text = _module(kernel=True).replace("/ssd", "")
    r = _reading(_events(1), hlo.Program(text).op_classes(), text)
    assert reader.read(r) is None
