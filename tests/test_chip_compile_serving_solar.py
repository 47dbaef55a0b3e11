"""``test_chip_compile_serving.py``'s cases of the hybrid of gated
grouped-query attention and 64-head Kimi Delta Attention (128 slots: state
pools of 541 MB a layer), in a file of their own."""

import pytest

from test_chip_compile_serving import (
    cases,
    serving_program_updates_the_pool_in_place,
)

pytestmark = pytest.mark.usefixtures("_no_compile_cache")


@pytest.mark.parametrize(**cases(["solar-open2-250b-ep8"]))
def test_serving_programs_update_the_pool_in_place(
        v5e, monkeypatch, config, program):
    serving_program_updates_the_pool_in_place(
        v5e, monkeypatch, config, program)
