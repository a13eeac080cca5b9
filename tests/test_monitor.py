"""Monitor backend: on-the-fly accumulation semantics, driven through the
``repro.session`` facade (the only supported construction path since the
legacy ``repro.core.TalpMonitor`` alias was removed)."""

import numpy as np
import pytest

from repro.core import GLOBAL_REGION, ResourceConfig, StepProfile, validate_pop
from repro.session import PerfSession, SessionConfig


def clocked_session(resources=None, **kw):
    t = [0.0]

    def clock():
        return t[0]

    ses = PerfSession(
        SessionConfig(app_name="t", backend="monitor", clock=clock,
                      sync_regions=False, respect_env=False, **kw),
        resources or ResourceConfig(num_hosts=2, devices_per_host=4),
    )
    return ses, t


def test_global_region_implicit_and_elapsed():
    ses, t = clocked_session()
    ses.start()
    t[0] = 5.0
    ses.stop()
    run = ses.finalize()
    assert run.regions[GLOBAL_REGION].measurements.elapsed_s == 5.0
    assert run.regions[GLOBAL_REGION].measurements.num_visits == 1


def test_region_accumulates_over_visits():
    ses, t = clocked_session()
    ses.start()
    for _ in range(3):
        with ses.region("timestep"):
            t[0] += 2.0
        t[0] += 1.0
    run_region = ses.finalize().regions["timestep"]
    assert run_region.measurements.elapsed_s == pytest.approx(6.0)
    assert run_region.measurements.num_visits == 3


def test_nested_regions_both_counted():
    ses, t = clocked_session()
    ses.start()
    with ses.region("outer"):
        t[0] += 1.0
        with ses.region("inner"):
            t[0] += 2.0
        t[0] += 1.0
    run = ses.finalize()
    assert run.regions["outer"].measurements.elapsed_s == pytest.approx(4.0)
    assert run.regions["inner"].measurements.elapsed_s == pytest.approx(2.0)


def test_observe_step_counts_and_device_time():
    ses, t = clocked_session()
    ses.start()
    with ses.region("step"):
        for _ in range(4):
            t[0] += 0.5  # device work
            ses.observe_step()
            t[0] += 0.25  # host-only gap
            ses.mark_device()
    m = ses.finalize().regions["step"].measurements
    assert m.num_steps == 4
    assert m.device_time_s == pytest.approx(2.0)
    assert m.elapsed_s == pytest.approx(3.0)


def test_lb_accumulators_sample_every_step_when_configured():
    ses, t = clocked_session(lb_sample_every=1)
    ses.start()
    with ses.region("step"):
        ses.observe_step(tokens_per_shard=[100, 50], expert_load=[3, 1, 0, 0])
        ses.observe_step(tokens_per_shard=[100, 100])
    m = ses.finalize().regions["step"].measurements
    assert m.data_lb == pytest.approx((0.75 + 1.0) / 2)
    assert m.expert_lb == pytest.approx(1.0 / 3)


def test_host_times_split_in_pod_inter_pod():
    ses, t = clocked_session(
        resources=ResourceConfig(num_hosts=4, devices_per_host=2, num_pods=2),
        lb_sample_every=1,
    )
    ses.start()
    with ses.region("step"):
        # pods: [1.0, 1.0] and [1.0, 2.0] -> in-pod mean(1, 0.75), inter 2/3...
        ses.observe_step(host_times=[1.0, 1.0, 1.0, 2.0], pod_size=2)
    m = ses.finalize().regions["step"].measurements
    assert m.in_pod_lb == pytest.approx((1.0 + 0.75) / 2)
    assert m.inter_pod_lb == pytest.approx(((1.0 + 2.0) / 2) / 2.0)


def test_static_counters_scale_with_steps():
    ses, t = clocked_session()
    prof = StepProfile(num_devices=8, flops=100.0, hbm_bytes=10.0,
                       collective_bytes_ici=1.0, model_flops=80.0)
    ses.attach_static("step", prof)
    ses.start()
    with ses.region("step"):
        for _ in range(5):
            ses.observe_step()
    run = ses.finalize()
    c = run.regions["step"].counters
    assert c.useful_flops == 500.0
    assert c.model_flops == 400.0
    # Global inherits child counters
    assert run.global_region.counters.useful_flops == 500.0


def test_finalized_pop_validates():
    ses, t = clocked_session(lb_sample_every=1)
    prof = StepProfile(num_devices=8, flops=1e12, hbm_bytes=1e10,
                       collective_bytes_ici=1e8)
    ses.attach_static("step", prof)
    ses.start()
    with ses.region("step"):
        t[0] += 1.0
        ses.observe_step(tokens_per_shard=[5, 10])
    for reg in ses.finalize().regions.values():
        assert validate_pop(reg.pop) == []


def test_monitor_overhead_is_o1_memory():
    """State size must not grow with steps (TALP's core property)."""
    ses, t = clocked_session(lb_sample_every=1)
    ses.start()
    mon = ses.collector  # the monitor backend's accumulator state
    with ses.region("step"):
        ses.observe_step(tokens_per_shard=[1, 2])
    size_10 = len(mon._regions)
    with ses.region("step"):
        for _ in range(1000):
            ses.observe_step(tokens_per_shard=[1, 2])
    assert len(mon._regions) == size_10  # no per-step state
    st = mon._regions["step"]
    assert isinstance(st.data_lb.total, float)  # scalar accumulators only


def test_peaks_are_looked_up_by_device_kind():
    from repro.core import hardware as HW

    # a v5e chip reports itself as "TPU v5 lite"
    assert HW.spec_for_kind("TPU v5 lite") is HW.TPU_V5E
    with pytest.raises(KeyError, match="TPU v99"):
        HW.spec_for_kind("TPU v99")


def test_record_off_the_chip_names_its_target_as_modeled():
    ses, t = clocked_session()
    ses.start()
    t[0] = 1.0
    run = ses.finalize()
    assert run.hardware == "tpu_v5e"
    assert run.metadata["hardware_basis"] == "modeled"
    ses, t = clocked_session(hardware="tpu_v5p")
    ses.start()
    assert ses.finalize().metadata["hardware_basis"] == "configured"
