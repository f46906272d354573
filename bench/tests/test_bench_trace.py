"""The reduction from a profiler trace to per-layer numbers, on a
synthetic `.xplane.pb` in the profiler's own format."""
import pytest

import tinycells  # noqa: F401
from benchlib import trace

PALLAS = ('%_chain_planes.1 = f32[2] custom-call(f32[2] %x), '
          'custom_call_target=\\"tpu_custom_call\\"')
TAIL = ('%bow_quantize_hist.1 = f32[2] custom-call(f32[2] %x), '
        'custom_call_target=\\"tpu_custom_call\\"')
TOPK = ('%custom-call = f32[2] custom-call(f32[8] %y), '
        'custom_call_target=\\"TopK\\"')
FUSION = "%fusion.3 = f32[2] fusion(f32[2] %y), kind=kLoop"


def _xspace(dev_events, host_events):
    """Text proto of one TPU plane and one host plane; times in us."""
    names = {}

    def meta(n):
        return names.setdefault(n, len(names) + 1)

    def line(evs):
        return "".join(
            f"    events {{ metadata_id: {meta(n)} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int(d * 1e6)} }}\n" for n, s, d in evs)

    dev = line(dev_events)
    dmeta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                    for n, i in names.items())
    names.clear()
    host = line(host_events)
    hmeta = "".join(f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                    for n, i in names.items())
    return (f'planes {{\n  id: 1\n  name: "/device:TPU:0"\n  lines {{\n    id: 1\n'
            f'    name: "XLA Ops"\n    timestamp_ns: 0\n{dev}  }}\n{dmeta}}}\n'
            f'planes {{\n  id: 2\n  name: "/host:CPU"\n  lines {{\n    id: 1\n'
            f'    name: "python"\n    timestamp_ns: 0\n{host}  }}\n{hmeta}}}\n')


@pytest.fixture
def tr(tmp_path):
    from jax.profiler import ProfileData
    # window 0..100 us; device: kernel 10-30, fusion 40-50 with a nested
    # top-k 42-48, tail kernel 90-95, an op outside the window 120-130
    dev = [(PALLAS, 10, 20), (FUSION, 40, 10), (TOPK, 42, 6),
           (TAIL, 90, 5), (FUSION, 120, 10)]
    host = [("bench.window", 0, 100), ("PjitFunction(scan)", 0, 35),
            ("np.stack", 50, 30), ("compile", 55, 10)]
    raw = ProfileData.text_proto_to_serialized_xspace(_xspace(dev, host))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    return trace.read(str(path))


def test_window_and_busy_union(tr):
    assert trace.window_s(tr) == pytest.approx(100e-6)
    # 10-30, 40-50 (the nested top-k counts once), 90-95
    assert trace.busy_ns(tr, 0) == pytest.approx(35e3)
    assert trace.busy_s(tr, [0]) == pytest.approx(35e-6)


def test_kernels_by_name(tr):
    assert trace.kernel_ns(tr, [0], ("_chain_planes",)) == pytest.approx(20e3)
    assert trace.kernel_ns(tr, [0], ("bow_quantize_hist", "linear_score")) \
        == pytest.approx(5e3)
    assert trace.kernel_ns(tr, [0], ("streaming_kernel",)) == 0
    # the TopK custom call is XLA's, not a Pallas kernel
    assert trace.busy_ns(tr, 0, trace.is_pallas) == pytest.approx(25e3)


def test_op_names():
    assert trace.op_name(PALLAS.replace('\\"', '"')) == "_chain_planes"
    assert trace.op_name(TOPK.replace('\\"', '"')) == "custom-call:TopK"
    assert trace.op_name(FUSION) == "fusion"


def test_breakdown(tr):
    ops = dict(trace.top_ops(tr, [0]))
    assert ops["_chain_planes"] == pytest.approx(20e-6)
    assert ops["fusion"] == pytest.approx(10e-6)      # the one in the window
    gaps = dict(trace.idle_gaps(tr, [0]))
    # a gap goes whole to the host event covering most of it: 0-10 and
    # 30-40 to PjitFunction, 50-90 to np.stack (30 us of it, against the
    # nested compile's 10), 95-100 to nothing
    assert gaps == pytest.approx({"PjitFunction(scan)": 20e-6,
                                  "np.stack": 40e-6, "host idle": 5e-6})


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.from_events({0: []}, [trace.Event(0, 1, "x")])


def _ctx(tr, images=4):
    from benchlib import harness, spec
    c = spec.Cell("sift1080p.b16", tinycells.ROOT)
    subs = [harness.Submit(0.0, 0.5, [0, 1], 0.4, 0),
            harness.Submit(0.5, 0.8, [2, 3], 0.1, 0)]
    return harness.Context(config=c.config, submits=subs, images=images,
                           devices=[0], device_kind="TPU v5 lite",
                           task=c.task, trace=tr)


def _readers():
    from benchlib import spec
    return {m["name"]: r for m, r in
            spec.Cell("bow250svm1080p.b16", tinycells.ROOT).per_layer()}


def test_readers_on_a_trace(tr):
    got = {k: r.read(_ctx(tr)) for k, r in _readers().items()}
    assert got["admission_ms.batch"] == pytest.approx(150.0)   # (100+200)/2
    assert got["stencil_ms_per_image"] == pytest.approx(20e-3 / 4)
    assert got["tail_ms_per_image"] == pytest.approx(5e-3 / 4)
    # busy 35 us, Pallas 25 us of it
    assert got["sift_xla_ms_per_image"] == pytest.approx(10e-3 / 4)
    assert got["device_idle_frac.batch"] == pytest.approx(0.65)
    least_s = 116121600 * 4 / 819e9
    assert got["stencil_roofline"] == pytest.approx(100 * least_s / 20e-6)


def test_readers_find_nothing_and_say_so():
    empty = trace.from_events({0: [trace.Event(10e3, 20e3, "%fusion = f32[2]")]},
                              [trace.Event(0, 100e3, trace.WINDOW_SPAN)])
    got = {k: r.read(_ctx(empty)) for k, r in _readers().items()}
    for k in ("stencil_ms_per_image", "stencil_roofline", "tail_ms_per_image"):
        assert got[k] is None, k
    assert got["sift_xla_ms_per_image"] == pytest.approx(10e-3 / 4)
