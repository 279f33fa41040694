"""The control (the reference one precision step down, in the program's
place) fails the comparison; the same comparison passes the program."""

import pytest

from bench import check, control, spec


@pytest.mark.parametrize("cell", ["tiny-dense.batch-t8", "tiny-cp.batch-t1",
                                  "tiny-dense.served"])
def test_control_fails_its_limits(tiny_root, cell):
    limits = spec.limits(cell, tiny_root / "bench")["numbers"]
    for seed in (1, 2):
        numbers = control.control_numbers(cell, seed, root=tiny_root)
        ok, _ = check.judge(numbers, {k: limits[k] for k in numbers})
        assert not ok, (seed, numbers)


def test_lower_precision_steps():
    assert control.LOWER["float32"] == "bfloat16"
    assert control.LOWER["bfloat16"] == "float8_e4m3fn"
