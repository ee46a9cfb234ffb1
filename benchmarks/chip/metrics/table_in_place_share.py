"""Wave program: the waves whose new state table is the old table's own
buffer (the serving metrics sink's ``table_in_place``), over the waves that
committed a table (``table_in_place`` + ``table_copied``), in %.  None
where the program keeps neither counter, or no wave committed one."""


def read(rec):
    t = rec.sink.get("state_transfer") or {}
    waves = t.get("table_in_place", 0) + t.get("table_copied", 0)
    if not waves:
        return None
    return 100.0 * t.get("table_in_place", 0) / waves
