"""The last line validates itself: every way a line can look right and be
wrong is refused with the field's name."""
import copy
import json

import pytest

from benchmark.harness import output

DECLARED = {"serve_tok_s": "tokens/s", "paged_append_roofline": "%"}
GOOD = {"correct": True, "attempted": 40, "failed": 0,
        "metrics": {"serve_tok_s": {"value": 41.5, "unit": "tokens/s"},
                    "paged_append_roofline": {"value": 37.0, "unit": "%"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 9861237248, "busy_s": 3.9,
                   "window_s": 4.0},
        "breakdown": {"device_ops": [["fusion.1", 1.5]],
                      "idle_gaps": [["bench:wait_for_token", 0.01]]}}


def edit(path, value):
    obj = copy.deepcopy(GOOD)
    node = obj
    for key in path[:-1]:
        node = node[key]
    if value is KeyError:
        del node[path[-1]]
    else:
        node[path[-1]] = value
    return obj


def test_good_line_round_trips():
    text = output.dumps(GOOD, DECLARED, True, 1)
    assert "\n" not in text and json.loads(text) == GOOD


@pytest.mark.parametrize("path,value,names", [
    (("correct",), "yes", "correct"),
    (("attempted",), KeyError, "attempted"),
    (("failed",), 41, "failed"),
    (("metrics", "serve_tok_s", "value"), float("nan"), "serve_tok_s"),
    (("metrics", "serve_tok_s", "value"), float("inf"), "serve_tok_s"),
    (("metrics", "serve_tok_s", "value"), None, "serve_tok_s"),
    (("metrics", "serve_tok_s", "unit"), "s", "serve_tok_s.unit"),
    (("metrics", "serve_tok_s"), KeyError, "serve_tok_s"),
    (("metrics", "extra"), {"value": 1.0, "unit": "ms"}, "extra"),
    (("metrics", "paged_append_roofline", "value"), 106.0, "roofline"),
    (("device", "kind"), "", "device.kind"),
    (("device", "count"), 0, "device.count"),
    (("device", "memory_peak_bytes"), 0, "memory_peak_bytes"),
    (("device", "busy_s"), 0.0, "busy_s"),
    (("device", "busy_s"), 4.5, "busy_s"),
    (("device", "busy_s"), KeyError, "busy_s"),
    (("device", "window_s"), float("nan"), "window_s"),
    (("breakdown", "device_ops"), [["x", 1.0]] * 11, "device_ops"),
    (("breakdown", "idle_gaps"), [["x", float("nan")]], "idle_gaps"),
])
def test_bad_line_is_refused_by_name(path, value, names):
    with pytest.raises(output.OutputError, match=names):
        output.dumps(edit(path, value), DECLARED, True, 1)


def test_untraced_line_needs_no_busy_time():
    obj = edit(("device", "busy_s"), KeyError)
    del obj["device"]["window_s"], obj["breakdown"]
    output.dumps(obj, DECLARED, False, 1)


def test_fewer_chips_than_the_cell_asks_is_refused():
    with pytest.raises(output.OutputError, match="device.count"):
        output.dumps(GOOD, DECLARED, True, 4)
