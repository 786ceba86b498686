"""`idle_by_span`'s `labels`, asked of SOME of the program's spans: the spans whose names
do not match `among` are taken out of the trace before the shortest open one is looked
for. Two averagers of one peer work side by side (since PR 36 the state round runs
behind the next epoch's steps and gradient round), and a question about the gradient
round must not be answered by whatever the state round has open at that moment: the
program's work spans say whose they are in their names (`hivemind:wire.encode.grads`).

Returns None where no span of the trace matches `present` (default: `labels`): a
program without such spans has not measured the thing, which is not a share of 0."""

import os
import re

from perf.readers import idle_by_span


def visible(planes, among):
    """`planes` less the program spans whose names (after the prefix) `among` does not match."""
    prefix, keep = idle_by_span.PROGRAM_PREFIX, re.compile(among)
    return {plane: {line: [event for event in events
                           if not event[0].startswith(prefix) or keep.search(event[0][len(prefix):])]
                    for line, events in lines.items()}
            for plane, lines in planes.items()}


def share(planes, labels, among, present=None, host_spans=()):
    planes = visible(planes, among)
    prefix, needed = idle_by_span.PROGRAM_PREFIX, re.compile(present or labels)
    names = {name[len(prefix):] for name, _start, _end in idle_by_span.host_annotations(planes)
             if name.startswith(prefix)}
    if not any(needed.search(name) for name in names):
        return None
    return idle_by_span.share(planes, labels=labels, host_spans=host_spans)


def read(obs, labels, among, present=None):
    if not obs.get("trace"):
        return None
    from perf import runtime

    path = idle_by_span.find_xplane(str(runtime.TRACE_DIR))
    if path is None:
        return None
    with runtime._SPANS_LOCK:
        host_spans = list(runtime._SPANS)
    return share(idle_by_span._planes(path, os.path.getmtime(path)), labels, among, present=present,
                 host_spans=host_spans)
