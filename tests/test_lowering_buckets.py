"""The lowering-bucket table is a contract: no shipped template may
silently change evaluation bucket (device-lowered / scalar-fallback /
rejected).  A regression that drops a template off the device engine —
or an unsound widening that suddenly "lowers" a scalar template — must
show up as a deliberate edit to lowering_buckets.json."""

from gatekeeper_tpu.library import LIBRARY
from gatekeeper_tpu.library.buckets import compute_buckets, load_committed


def test_no_template_silently_changes_bucket():
    computed = compute_buckets()
    committed = load_committed()
    diffs = []
    for name in sorted(set(computed) | set(committed)):
        got = computed.get(name, "<template removed>")
        want = committed.get(name, "<not in committed table>")
        if got != want:
            diffs.append(f"  {name}: committed={want!r} computed={got!r}")
    assert not diffs, (
        "template lowering buckets changed — if deliberate, regenerate "
        "the table with `python -m gatekeeper_tpu.library.buckets`:\n"
        + "\n".join(diffs))


def test_no_rejected_templates_in_corpus():
    assert not [k for k, v in compute_buckets().items()
                if v.startswith("rejected")]


def test_every_library_template_is_device_lowered():
    committed = load_committed()
    assert {k: committed[k] for k in LIBRARY
            if committed[k] != "device-lowered"} == {}
    assert len(LIBRARY) == 49
