"""Closed loop: ``clients`` clients (a key of the mix), each with one
request outstanding; a client sends its next request ``think_s`` (0 where
the mix gives none) after its previous one finished. A request's arrival
is when its client was due to send it. Requests are the schedule's pool,
taken in order and cycled; each sending has a uid of its own. Between
sendings the system does one unit of work, or the driver sleeps until a
client is due."""
from bench.harness import clock, sleep_until


def drive(system, schedule, win, rec):
    reqs = schedule.requests
    clients = int(schedule.params["clients"])
    think = float(schedule.params.get("think_s", 0.0))
    due = [win.start] * clients
    waiting = [None] * clients            # the uid each client waits on
    i = 0
    while win.tick(clock()):
        now = clock()
        for c in range(clients):
            u = waiting[c]
            if u is not None and u in rec.done:
                due[c], waiting[c] = rec.done[u] + think, None
            if waiting[c] is None and due[c] <= now:
                rec.arrival[i] = due[c]
                system.submit(reqs[i % len(reqs)], uid=i)
                win.lateness_s.append(now - due[c])
                waiting[c] = i
                i += 1
        win.submitted = i
        if system.busy():
            system.pump()
        else:
            idle = [due[c] for c in range(clients) if waiting[c] is None]
            sleep_until(min(idle + [win.end]))
