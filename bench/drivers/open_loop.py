"""Open loop: each request is submitted at its scheduled time, whatever
the server does; its arrival is that scheduled time, so a stall counts
against the requests that wait behind it. Between submissions the
system does one unit of work, or the driver sleeps until the next
arrival."""
from bench.harness import clock, sleep_until


def drive(system, schedule, win, rec):
    reqs = schedule.requests
    i = 0
    while win.tick(clock()):
        now = clock()
        while i < len(reqs) and win.start + reqs[i].t <= now:
            r = reqs[i]
            due = win.start + r.t
            rec.arrival[r.uid] = due
            system.submit(r, uid=r.uid)
            win.lateness_s.append(now - due)
            i += 1
        win.submitted = i
        if system.busy():
            system.pump()
        else:
            nxt = win.start + reqs[i].t if i < len(reqs) else win.end
            sleep_until(min(nxt, win.end))
