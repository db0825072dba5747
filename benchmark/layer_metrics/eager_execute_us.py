"""Layer: eager plane.  The dispatcher's work for one response: mean
time from the start of its execution to its last request's result
(``t_done - t_execute_start`` by response id in the program's request
log), over the responses of the measured window, in microseconds."""


def read(run):
    responses = {}
    for _, response, _, _, start, done in run.reader(
            ".", "program_trace").request_log(run):
        responses[response] = max(responses.get(response, 0), done - start)
    if not responses:
        return None
    return sum(responses.values()) / len(responses) / 1e3
