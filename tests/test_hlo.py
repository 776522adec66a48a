"""Collectives read from a compiled program's HLO text (repro.obs.hlo)."""

import pytest

from repro.obs import hlo

# a layer loop of 3 trips (the count only in its condition, as the TPU
# compiler leaves it) holding two all-reduces, an async all-gather
# outside it, and a reducer that holds no collective
TEXT = """HloModule jit_tick, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%x, %y)
}

%body (p: (s32[], bf16[16,4096])) -> (s32[], bf16[16,4096]) {
  %p = (s32[], bf16[16,4096]) parameter(0)
  %all-reduce.1 = bf16[16,4096]{1,0} all-reduce(%a), channel_id=1, to_apply=%add
  %all-reduce.2 = bf16[16,4096]{1,0} all-reduce(%b), channel_id=2, to_apply=%add
  ROOT %t = (s32[], bf16[16,4096]) tuple(%i, %all-reduce.2)
}

%cond (p: (s32[], bf16[16,4096])) -> pred[] {
  %constant.9 = s32[]{:T(128)} constant(3)
  %p = (s32[], bf16[16,4096]) parameter(0)
  %gte = s32[] get-tuple-element(%p), index=0
  ROOT %lt = pred[] compare(%gte, %constant.9), direction=LT
}

ENTRY %main (a: bf16[16,1024]) -> bf16[16,4096] {
  %a = bf16[16,1024]{1,0} parameter(0)
  %all-gather-start = (bf16[16,1024]{1,0}, bf16[16,4096]{1,0}) all-gather-start(%a), dimensions={1}
  %all-gather-done = bf16[16,4096]{1,0} all-gather-done(%all-gather-start)
  %while.1 = (s32[], bf16[16,4096]) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[16,4096]{1,0} get-tuple-element(%while.1), index=1
}
"""


def test_counts_each_loop_trip_and_async_pairs_once():
    assert hlo.collectives(TEXT) == {
        "all-gather": (1, 16 * 4096 * 2),
        "all-reduce": (6, 6 * 16 * 4096 * 2),
    }
    assert hlo.summary(TEXT) == (7, 7 * 16 * 4096 * 2)


def test_known_trip_count_wins():
    text = TEXT.replace(
        "body=%body",
        'body=%body, backend_config={"known_trip_count":{"n":"40"}}')
    assert hlo.collectives(text)["all-reduce"][0] == 80


@pytest.mark.parametrize("text", ["", "HloModule m\n"])
def test_no_program_no_collectives(text):
    assert hlo.collectives(text) == {}
    assert hlo.summary(text) == (0, 0)
