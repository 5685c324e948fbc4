"""Work counts and roofline arithmetic."""
import pytest

from perfbench import peaks, work

V5E = peaks.lookup("TPU v5 lite")


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("TPU v99")


def test_mnist_ingest_byte_floor():
    # K = 1024, D = 794: the symmetric half of Λ less VMEM, per point
    t = work.least_seconds(work.ingest_flops(1024, 794, 1),
                           work.ingest_bytes(1024, 794, 1, V5E.vmem_bytes),
                           V5E)
    assert t == pytest.approx(1.42e-3, rel=0.01)


def test_a_body_with_no_hbm_traffic_reads_at_most_100pct():
    # a pool small enough for VMEM: the byte term is the rows alone, and
    # a body running at the FLOP peak reads exactly 100%
    k, d, n = 4, 64, 1000
    assert work.state_bytes(k, d) < V5E.vmem_bytes
    flops = work.ingest_flops(k, d, n)
    nbytes = work.ingest_bytes(k, d, n, V5E.vmem_bytes)
    assert nbytes == 4 * d * n
    fastest = max(flops / V5E.flops, nbytes / V5E.hbm_bw)
    assert work.roofline_pct(flops, nbytes, fastest, V5E) == \
        pytest.approx(100.0)
    assert work.roofline_pct(flops, nbytes, 2 * fastest, V5E) == \
        pytest.approx(50.0)


def test_reads_count_the_state_once_per_dispatch():
    one = work.read_bytes(1024, 794, 1, 0, V5E.vmem_bytes)
    assert work.read_bytes(1024, 794, 3, 0, V5E.vmem_bytes) == 3 * one
    assert work.read_bytes(1024, 794, 1, 10, V5E.vmem_bytes) == one + 40
    assert work.read_flops(1024, 794, 2) == 2 * 2 * 1024 * 794 ** 2


def test_no_device_time_reads_nothing():
    assert work.roofline_pct(1.0, 1.0, 0.0, V5E) is None
