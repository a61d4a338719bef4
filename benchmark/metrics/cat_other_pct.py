"""Share of the categorical cells of the ingested tables whose id is in no
bin of its column (it went to "other", bin 0, with the missing ones), by
the program's own counters: `ingest.cat_other` over `ingest.cat_cells`.
None where the program keeps no such counters or ingested no categorical
column on the device."""


def read(ctx):
    try:
        from lightgbm_tpu import obs
        reg = obs.registry()
        other = reg.get("ingest.cat_other")
        cells = reg.get("ingest.cat_cells")
    except (ImportError, AttributeError):
        return None
    if other is None or cells is None or not cells.value:
        return None
    return 100.0 * other.value / cells.value
