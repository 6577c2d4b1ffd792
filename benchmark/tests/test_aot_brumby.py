"""The step programs of ``brumby_doc_reports`` compiled for the real chip at
the cell's real sizes (8 layers of power retention at 40 query heads on 8
key/value heads of 128, a float32 state of 34,080,768 B a (slot, layer),
16 slots; a mixed step of 528 packed rows, the one-token step and the
``multi_step`` scan of stride 4) by the TPU compiler that is installed
here, for a v5e that is described and not attached. Nothing runs: a
compile that passes is not a chip run. The topology is described inside a
fixture, all in this one file (``brumby_aot.py`` beside it holds what the
tier-1 case in ``tests/test_brumby.py`` shares). A program compiles in 45
to 80 s.

What is held: the programs compile in plain XLA at the published widths;
the state is the chip's largest tenant after the weights (4.36 GB beside
8.40 GB) and no instruction makes a second state-shaped array but the
core's own update, where it lies (every state argument is aliased to its
output); arguments and temporaries fit the chip; there is no pool."""
import pytest

from benchmark.tests import brumby_aot

#: the mixed step's temporaries are 0.16 GB and the scan's 0.67 GB (one
#: state-sized buffer the compiler keeps for the loop)
TEMPORARIES_BOUND = 1.0e9


@pytest.fixture(scope="module")
def one_chip():
    try:
        return brumby_aot.describe_one_chip()
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def engine(one_chip):
    return brumby_aot.engine(one_chip)


def test_the_cells_sizes(engine):
    eng, _, _ = engine
    assert eng.B == 16 and eng.capacity == 8704 == 17 * 512
    assert eng.mixed_rows == 528
    assert len(eng._k) == 8 and all(v is None for v in eng._v)
    assert {tuple(s["S"].shape) for s in eng._k} == {(16, 8, 8256, 128)}
    assert {tuple(s["z"].shape) for s in eng._k} == {(16, 8, 8256)}
    # no pool: the state is the tenant, 34,080,768 B a (slot, layer)
    assert eng.kv_pool_nbytes() == 0
    assert sum(k.bytes_per_slot() for k in eng._layout) * eng.B \
        == 16 * 8 * brumby_aot.STATE_BYTES == 4_362_338_304


@pytest.mark.parametrize("name", ["fused_step", "step", "multi_step"])
def test_a_step_program_compiles_with_the_state_updated_in_place(engine,
                                                                  name):
    eng, raw, args = engine
    compiled = brumby_aot.compile_for_the_chip(raw, args, name)
    arguments, temporaries = brumby_aot.held_in_place(compiled, eng)
    # the arguments: the states and 4,198,652,928 parameters of 2 B (and
    # the rotary table, the logits, the lengths)
    assert arguments == pytest.approx(
        4_362_338_304 + 2 * 4_198_652_928, rel=0.01)
    assert temporaries < TEMPORARIES_BOUND
    assert "tpu_custom_call" not in compiled.as_text()     # plain XLA
