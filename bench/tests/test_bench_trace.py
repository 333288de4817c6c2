"""The trace reduction on a small trace with known answers."""
from types import SimpleNamespace as NS

from jax.profiler import ProfileData

from bench import xplane

# one device plane, one host thread; times in ns from the lines' stamps
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000
             stats { metadata_id: 9 str_value: "jit(w)/pallas_call" } }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 6000000 duration_ps: 1000000
             stats { metadata_id: 9 str_value: "jit(w)/coax_fused_scan" } }
    events { metadata_id: 2 offset_ps: 9000000 duration_ps: 5000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 13000000 } }
  event_metadata { key: 1 value { id: 1 name: "%coax_fused_scan.1 = (s32[8]) custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2 = s32[8] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "jit__wave_program" } }
  stat_metadata { key: 9 value { id: 9 name: "long_name" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 500000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.drain" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_wave_program)" } }
}
"""


def test_reduce_known_trace():
    r = xplane.reduce(ProfileData.from_text_proto(TRACE), "coax_fused_scan")
    # window [1000, 11000); ops [1000,3000) [2000,4000) [7000,8000) and
    # [10000,15000) clipped to [10000,11000): busy 3000 + 1000 + 1000
    assert r["window_s"] == 10000e-9
    assert r["busy_s"] == 5000e-9
    assert r["kernel_s"] == 3000e-9 and r["kernel_events"] == 2
    assert r["device_planes"] == 1
    ops = dict(r["breakdown"]["device_ops"])
    assert ops == {"coax_fused_scan.1": 3000e-9, "fusion.2": 3000e-9}
    gaps = r["breakdown"]["idle_gaps"]
    # idle [4000,7000) [8000,10000); the first's middle, 5500, lies in
    # bench.drain and in the wave-program call inside it; the second's
    # only in the window
    assert gaps == [["bench.drain > PjitFunction(_wave_program)", 3000e-9],
                    ["bench.window", 2000e-9]]


def test_reduce_without_device_or_window():
    ev = NS(name="fusion", start_ns=0.0, duration_ns=10.0, stats=[])
    pd = NS(planes=[NS(name="/device:TPU:0",
                       lines=[NS(name="XLA Ops", events=[ev])])])
    r = xplane.reduce(pd, "coax_fused_scan")
    assert r["window_s"] == 10e-9 and r["busy_s"] == 10e-9
    assert r["kernel_s"] == 0.0
    empty = xplane.reduce(NS(planes=[]), "coax_fused_scan")
    assert empty["device_planes"] == 0 and empty["busy_s"] == 0.0
