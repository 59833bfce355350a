"""Engine launches per whole call in the window.

Source: the program's counter ``edm_launches`` (one per batched engine
launch of ``core.ccm.drive_batched``), its delta across the window,
over the whole calls the window ran. Reads no peak.
"""


def read(ctx):
    calls = ctx["outcome"].attempted
    if not calls:
        return None
    return ctx["window"].counters.get("edm_launches", 0) / calls
