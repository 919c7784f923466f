"""``chip_smoke.py``'s hybrid-decoder phases rehearsed on the CPU mesh:
the second of the two one-chip cases of ``tests/test_chip_smoke.py``, in
a file of its own so that ``--dist loadfile`` can hand it to another
worker."""
from __future__ import annotations

import pytest
from test_chip_smoke import (  # noqa: F401  (the fixtures, by name)
    HYBRIDS, check_tiny_phases, smoke, smoke_out)


@pytest.mark.parametrize("phases", [HYBRIDS], ids=["one_chip_hybrids"])
def test_phases_pass_at_tiny_size_and_exit_nonzero_off_tpu(
        smoke_out, capsys, eight_devices, phases):
    check_tiny_phases(smoke_out, capsys, ["--phases", ",".join(phases)],
                      phases)
