"""Pack slots the executor scanned per frame the queries overlap: the
program's ``packs_scanned`` times the pack capacity, over the frames the
benchmark's own geometry finds under each box.  1.0 means no masked slot
was scanned."""

NAME = "exec.slots_per_frame.stack"
UNIT = "slots/frame"
LAYER = "executor (core/engine.py execute)"
MOVES = "frames_per_s"
SOURCE = "program_counter"


def read(record, trace):
    qs = [q for q in record.get("queries", []) if q["dispatched"]]
    frames = sum(q["frames"] for q in qs)
    if not frames:
        return None
    return sum(q["packs_scanned"] for q in qs) * record["capacity"] / frames
