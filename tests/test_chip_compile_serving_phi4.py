"""``test_chip_compile_serving.py``'s cases of the decoder-hybrid-decoder
(32 layers of five kinds: a long compile), in a file of their own."""

import pytest

from test_chip_compile_serving import (
    cases,
    serving_program_updates_the_pool_in_place,
)

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


@pytest.mark.parametrize(**cases(["phi4-mini-flash-3p8b"]))
def test_serving_programs_update_the_pool_in_place(
        v5e, monkeypatch, config, program):
    serving_program_updates_the_pool_in_place(
        v5e, monkeypatch, config, program)
