"""The ``ouro`` family's looped member model (the held layers run four times
over one set of weights, the exit gate's probabilities and the steps' gains)
against its plain reference (``benchmark/reference``) at the family's tiny
cut, under both bodies of the token mixers: the laws of ``_token_lm.py``.
The family's other laws are in ``test_ouro_lm.py``."""

from __future__ import annotations

import pytest

from _token_lm import (
    FLOAT32_CASES,
    a_token_sees_only_its_document_and_its_past,
    bfloat16_operands_stay_near_the_reference,
    body,  # noqa: F401  (the fixture)
    program_agrees_with_the_reference_in_float32,
)

FAMILY = "ouro"


@pytest.mark.parametrize("rank,layers,steps", FLOAT32_CASES[FAMILY])
def test_program_agrees_with_the_reference_in_float32(rank, layers, steps, body):  # noqa: F811
    program_agrees_with_the_reference_in_float32(FAMILY, rank, layers, steps)


def test_bfloat16_operands_stay_near_the_reference(body):  # noqa: F811
    bfloat16_operands_stay_near_the_reference(FAMILY)


def test_a_token_sees_only_its_document_and_its_past(body):  # noqa: F811
    a_token_sees_only_its_document_and_its_past(FAMILY)
