"""The program's own spans as the benchmark's trace reduction sees them:
inside the harness's span around the timed call, an idle gap goes to the
innermost `bloom.*` span over its midpoint; and the kernel names that the
roofline reader finds."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from bench import generate, run, systems, trace_reduce
from bench.tests import tiny

# times in ps from 0; the window is [0, 20) us, the harness's call [1, 19)
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 4000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "multihash.1" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 18000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 18000000 }
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 6 offset_ps: 11000000 duration_ps: 1000000 }
    events { metadata_id: 7 offset_ps: 12000000 duration_ps: 7000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "window" } }
  event_metadata { key: 2 value { id: 2 name: "check_and_add_batch" } }
  event_metadata { key: 3 value { id: 3 name: "bloom.check_and_add_batch" } }
  event_metadata { key: 4 value { id: 4 name: "bloom.stage" } }
  event_metadata { key: 5 value { id: 5 name: "bloom.stage.pad" } }
  event_metadata { key: 6 value { id: 6 name: "bloom.launch" } }
  event_metadata { key: 7 value { id: 7 name: "bloom.readback" } }
}
"""

# every span of one routed admission call: name -> its parent
NESTING = {
    "bloom.check_and_add_batch": None,
    "bloom.stage": "bloom.check_and_add_batch",
    "bloom.stage.stack": "bloom.stage",
    "bloom.stage.pad": "bloom.stage",
    "bloom.stage.to_device": "bloom.stage",
    "bloom.launch": "bloom.check_and_add_batch",
    "bloom.readback": "bloom.check_and_add_batch",
}


def test_idle_gaps_go_to_the_program_spans():
    pdata = ProfileData.from_text_proto(XSPACE)
    r = trace_reduce.reduce(pdata, trace_reduce.find_span(pdata, "window"))
    assert r.busy_s == pytest.approx(4e-6)
    # [0, 12) us: midpoint 6 us, inside the pad; [16, 20): midpoint 18 us,
    # inside the readback; the harness's own span names neither
    assert dict(r.idle_gaps) == pytest.approx({"bloom.stage.pad": 12e-6,
                                               "bloom.readback": 4e-6})


def _within(inner, outer) -> bool:
    return outer.start_ns <= inner.start_ns and inner.end_ns <= outer.end_ns


@pytest.mark.parametrize("name", ["dedup_docs", "dedup_paragraphs"])
def test_cell_call_writes_the_program_spans(interpret, tmp_path, name):
    """One wave of the tiny cell through the system the benchmark builds,
    under the profiler and the harness's span: every span of the program's
    call lies inside the harness's, as the reduction reads them back."""
    cell = tiny.cell(name)
    sysobj = systems.build(cell.cfg, cell.chips, 2**31 + 7)
    traffic = generate.Traffic(cell.mix, 2**31 + 7, 2)
    sysobj.call(traffic.items(traffic.wave(0)))  # compile outside the trace
    rows = traffic.items(traffic.wave(1))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as the harness traces its window
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with run._span(True, sysobj.entry):
            sysobj.call(rows)
    finally:
        jax.profiler.stop_trace()
    path = next(os.path.join(b, f) for b, _, fs in os.walk(tmp_path)
                for f in fs if f.endswith(".xplane.pb"))
    host = trace_reduce.host_events(trace_reduce.load(path))
    outer = [e for e in host if e.name == sysobj.entry]
    spans = {e.name: e for e in host if e.name.startswith("bloom.")}
    assert len(outer) == 1 and set(spans) == set(NESTING)
    for span, parent in NESTING.items():
        assert _within(spans[span], spans[parent] if parent else outer[0])
    pad = spans["bloom.stage.pad"]
    index = trace_reduce._HostIndex(host)
    assert index.innermost((pad.start_ns + pad.end_ns) / 2) == pad.name


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        elif "jaxpr" in eqn.params:  # into the kernel entry's jit
            out += _pallas_names(eqn.params["jaxpr"].jaxpr)
    return out


def test_kernel_names_match_the_roofline_reader():
    from repro.kernels import gf_multihash as gfmh, multihash as mhk

    spec = importlib.util.spec_from_file_location(
        "roof", f"{run.ROOT}/bench/metrics/multihash_roofline.py")
    roof = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(roof)
    t, k = jnp.zeros((8, 1024), jnp.uint32), jnp.zeros((2, 1024), jnp.uint32)
    lens, m1 = jnp.zeros(8, jnp.int32), jnp.zeros((2, 2), jnp.uint32)
    names = (
        _pallas_names(jax.make_jaxpr(mhk.multihash_blocks)(t, k, k, lens, m1))
        + _pallas_names(jax.make_jaxpr(gfmh.gf_multihash_blocks)(
            t, k, lens, m1)))
    assert names == ["multihash", "gf_multihash"]
    assert all(roof.KERNEL.search(n) for n in names)
