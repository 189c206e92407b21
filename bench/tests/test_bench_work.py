"""The least-work counts behind every roofline share, pinned to shapes."""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import peaks  # noqa: E402
from work import ebe_product, host_link, multispring_update  # noqa: E402

E, N, S, P = 63888, 91125, 150, 4  # basin22-ebe


def test_ebe_product_counts_symmetric_tangent_and_nodal_vectors_once():
    flops, nbytes = ebe_product.count(E, N)
    d_bytes = 4 * 21 * P * E        # the tangent: the largest stream
    x_bytes = 4 * 3 * N             # one nodal vector
    assert nbytes == d_bytes + 2 * x_bytes
    assert d_bytes / nbytes > 0.9
    assert flops == 2 * (90 + 36 + 90) * P * E
    # one more element adds its tangent; one more node adds x in and y out
    assert ebe_product.count(E + 1, N)[1] - nbytes == 4 * 21 * P
    assert ebe_product.count(E, N + 1)[1] - nbytes == 4 * 6


def test_multispring_counts_history_words_and_one_bit_flags_read_and_written():
    flops, nbytes = multispring_update.count(E, S)
    per_spring = 2 * (4 * 4 + 2 / 8)          # fp32 history, 1-bit flags, r+w
    per_point = 4 * (6 + 6 + 21 + 1)          # ε in; σ, symmetric D, frac out
    assert nbytes == pytest.approx(E * P * (S * per_spring + per_point))
    assert per_spring == 32.5
    # fp32: 4-byte words, not float64's 8 nor the int32 flags' 24 B spring
    assert nbytes < E * P * S * 2 * 24
    assert flops == 2 * (6 + 6 + 21) * S * E * P


def test_host_link_counts_both_directions_of_host_leaves_only():
    leaf = lambda nb, kind: types.SimpleNamespace(
        nbytes=nb, sharding=types.SimpleNamespace(memory_kind=kind))
    leaves = [leaf(100, "pinned_host"), leaf(7, "device"), leaf(50, "pinned_host")]
    assert host_link.bytes_per_step(leaves) == 300.0


@pytest.mark.parametrize("name, to_device, nbytes", [
    ("%copy-start = (f32[12288,150]{0,1:T(8,128)}, f32[12288,150]{0,1:T(8,128)S(5)}, "
     "u32[]{:S(2)}) copy-start(f32[12288,150]{0,1:T(8,128)S(5)} %carry_1__0__0__0_.1)",
     True, 12288 * 150 * 4),
    ("%copy-start.48 = (s32[12288,150]{0,1:T(8,128)S(5)}, s32[12288,150]{0,1:T(8,128)}, "
     "u32[]{:S(2)}) copy-start(s32[12288,150]{0,1:T(8,128)} %bitcast.1049)",
     False, 12288 * 150 * 4),
    ("%copy-start.7 = (u8[3,5]{1,0}, u8[3,5]{1,0:S(5)}, u32[]{:S(2)}) "
     "copy-start(u8[3,5]{1,0:S(5)} %f)", True, 15),
])
def test_a_traced_copy_gives_its_direction_and_logical_bytes(name, to_device, nbytes):
    """The layout's tile padding (12288 x 150 fills 12288 x 152) is not
    counted: the bytes are what the program asked to move."""
    assert host_link.from_host(name) is to_device
    assert host_link.copy_bytes(name) == nbytes


def test_roofline_share_names_its_bound_and_knows_its_devices():
    fl, by = ebe_product.count(E, N)
    t_mem = by / 819e9
    share, bound = peaks.roofline_share(fl, by, t_mem, "TPU v5 lite")
    assert bound == "memory" and share == pytest.approx(100.0)
    share, bound = peaks.roofline_share(1e12, 1.0, 1.0, "TPU v5 lite")
    assert bound == "compute" and share == pytest.approx(100 * 1e12 / 197e12)
    with pytest.raises(KeyError):
        peaks.peaks("some other accelerator")
