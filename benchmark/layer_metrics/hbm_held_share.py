"""What the fullest chip holds: the allocator's live peak plus the chunk
program's temporaries (``memory_analysis()``), over the device's limit."""


def read(facts):
    m = facts["memory"]
    if not m["bytes_limit"] or not m["program_temp_bytes"]:
        return None
    return 100.0 * (m["live_peak_bytes"] + m["program_temp_bytes"]) / m["bytes_limit"]
