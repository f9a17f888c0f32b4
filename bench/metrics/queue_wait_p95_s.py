"""Frontend admission: the 95th percentile of the wait from when the frontend
accepted a request (the program's ``Request.submitted_s``) to when admission
took it into a slot (``admitted_s``), over every request accepted in the
window; one not admitted by the window's close enters with its wait so far.
A program that does not stamp its requests reads nothing."""
from clientstats import percentile


def read(ctx):
    w0, w1 = ctx["window"]
    waits = []
    for r in ctx["records"]:
        q = r.req
        t0 = getattr(q, "submitted_s", None)
        if t0 is None or not w0 <= t0 < w1:
            continue
        t1 = q.admitted_s
        waits.append((t1 if t1 is not None and t1 <= w1 else w1) - t0)
    return percentile(waits, 95)
