"""Host data plane: bytes read from the file over the bytes of records
delivered, (index_bytes + payload_bytes) / payload_bytes, from the
counters on the window's `data.read` spans (one a task: the task's
records, payload bytes, the index bytes its open re-read, its opens).
1.0 is a reader that reads each record once and nothing else.  Nothing
where the journal has no such span."""

from lib import journal


def read(run):
    index = payload = 0
    for e in journal.spans(run.worker, "data.read"):
        if run.t0 < e["ts"] <= run.t1:
            index += e.get("index_bytes", 0)
            payload += e.get("payload_bytes", 0)
    if not payload:
        return None
    return (index + payload) / payload
