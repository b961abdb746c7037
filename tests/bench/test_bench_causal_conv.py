"""``causal_conv_ms`` and ``causal_conv_kernel_pct``: the causal
convolutions' device time and the Mosaic kernels' share of it, on a small
synthetic HLO module and trace."""
import pytest

from bench_fixtures import import_harness
from test_bench_scopes import _reading

harness, spec = import_harness()
import hlo  # noqa: E402

CELL = "mamba2-130m.train.s2048"
CONV = "jit(train_step)/jvp()/while/body/closed_call/ssm/causal_conv"
CONV_BWD = ("jit(train_step)/transpose(jvp())/while/body/closed_call/"
            "checkpoint/ssm/transpose(jvp(causal_conv))")


def _module(kernel: bool, jnp_conv: bool = True) -> str:
    """A plain ``causal_conv`` fusion (or, without ``jnp_conv``, one more
    kernel), a forward and a backward ``causal_conv`` op that are Mosaic
    kernels or plain fusions, and an ``ssm`` op outside the convs."""
    def op(name, op_name, mosaic):
        call = ('custom-call(%p), custom_call_target="tpu_custom_call"'
                if mosaic else "fusion(%p), kind=kLoop, calls=%f")
        return (f'  %{name} = f32[8]{{0}} {call}, '
                f'metadata={{op_name="{op_name}" source_file="m.py" '
                f'source_line=3}}')
    return "\n".join([
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        "  %p = f32[8]{0} parameter(0)",
        op("fusion.1", CONV + "/add", not jnp_conv),
        op("conv.2", CONV + "/pallas_call", kernel),
        op("conv.3", CONV_BWD + "/pallas_call", kernel),
        op("fusion.4", "jit(train_step)/jvp()/while/body/closed_call/ssm/mul",
           False),
        "  ROOT %t = f32[8]{0} copy(%fusion.4)",
        "}"])


def _events(cores):
    """Per core (op, start, end) in ns: the conv fusion 100, the forward
    conv 300, the backward conv 600, the other op 1000."""
    evs = [("fusion.1", 0.0, 100.0), ("conv.2", 100.0, 400.0),
           ("conv.3", 400.0, 1000.0), ("fusion.4", 1000.0, 2000.0)]
    return {"devices": {f"/device:TPU:{i}": list(evs) for i in range(cores)},
            "host": [("window", 0.0, 2000.0)]}


@pytest.fixture(scope="module")
def readers():
    r = spec.load_cell(CELL).readers
    return r["causal_conv_ms"], r["causal_conv_kernel_pct"]


def _read(readers, text, cores=1):
    r = _reading(_events(cores), hlo.Program(text).op_classes(), text)
    return tuple(m.read(r) for m in readers)


@pytest.mark.parametrize("cores", [1, 4])
def test_kernels_share_of_conv_time(readers, cores):
    # 900 of the convs' 1,000 ns in kernels; the ssm op is not a conv's
    ms, pct = _read(readers, _module(kernel=True), cores)
    assert pct == pytest.approx(90.0)
    assert ms == pytest.approx(1e-3)


def test_kernels_read_all_of_their_own_ops(readers):
    ms, pct = _read(readers, _module(kernel=True, jnp_conv=False))
    assert pct == pytest.approx(100.0)
    assert ms == pytest.approx(1e-3)


def test_jnp_conv_reads_zero(readers):
    ms, pct = _read(readers, _module(kernel=False))
    assert pct == 0.0
    assert ms == pytest.approx(1e-3)


def test_silent_without_conv_scope(readers):
    text = _module(kernel=True).replace("/causal_conv", "").replace(
        "transpose(jvp(causal_conv))", "transpose(jvp())")
    assert _read(readers, text) == (None, None)


def test_metrics_only_in_the_mamba2_cell():
    assert "causal_conv_ms" not in spec.load_cell(
        "qwen2-0.5b.train.s4096").readers
