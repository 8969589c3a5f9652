"""The reader of the program's host ``eigh`` spans, on hand-built span
lists whose answers are known."""
import pytest

import tiny  # noqa: F401  (puts the harness on the path)
import spec


def _span(sid, name, t0, t1, parent=None, **attrs):
    return {"type": "span", "id": sid, "parent": parent, "name": name,
            "t0": t0, "t1": t1, "attrs": attrs}


# two rounds of one client each: select [1, 3] holds an eigh [1.5, 2.0]
# beside a compile span; select [5, 6.5] holds an eigh [5.25, 5.5]
SPANS = [
    _span(1, "round", 0.0, 4.0, round=1),
    _span(2, "select", 1.0, 3.0, parent=1, clients=1),
    _span(3, "compile", 1.1, 1.2, parent=2, phase="trace", fn="add"),
    _span(4, "eigh", 1.5, 2.0, parent=2, n=2500, p=200, dtype="float32",
          clients=1),
    _span(5, "round", 4.5, 8.0, round=2),
    _span(6, "select", 5.0, 6.5, parent=5, clients=1),
    _span(7, "eigh", 5.25, 5.5, parent=6, n=2500, p=200, dtype="float32",
          clients=1),
]


def _read(ctx):
    return spec.load_reader("eigh_host_s_per_update")(ctx)


def test_eigh_host_s_per_update_sums_the_eigh_spans():
    # 0.5 + 0.25 s over 2 updates
    assert _read({"spans": SPANS, "updates": 2}) == pytest.approx(0.375)


@pytest.mark.parametrize("ctx", [
    {"spans": [s for s in SPANS if s["name"] != "eigh"], "updates": 2},
    {"spans": [], "updates": 2},
    {"spans": SPANS, "updates": 0}], ids=["no_eigh", "no_spans", "no_updates"])
def test_nothing_to_read(ctx):
    """A program whose eigh runs on the device (no ``eigh`` spans), an
    untraced run, or a window without updates reads nothing."""
    assert _read(ctx) is None
