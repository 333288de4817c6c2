"""Share of the table's tiles the wave kernel read for the window's queries.

Layer: device plan (``engine/device.py``).  Source: the program's
``device.inputs`` spans recorded during the window (``repro.obs``
tracing), one a wave, whose args count ``tiles_listed`` (the work-list
items of every grid segment, a segment scanned in full counting its whole
image) and ``tiles_image`` (real queries x image tiles, summed over the
same segments).  100 x the first sum over the second.  A program without
those args reports nothing.
"""


def read(ctx):
    listed = image = 0
    for e in ctx.spans:
        if e["name"] == "device.inputs" and "tiles_image" in e["args"]:
            listed += e["args"]["tiles_listed"]
            image += e["args"]["tiles_image"]
    return 100.0 * listed / image if image else None
