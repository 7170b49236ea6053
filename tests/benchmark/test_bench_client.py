"""The client's handling of one call, against a stand-in for the gateway."""

import asyncio

from benchmark import client, schedule

SCHED = schedule.Schedule(
    name="t", loop="closed", clients=1, pairs=((4, 10),), offsets=(0,),
    session_turns=1, shared_prefix_tokens=0, think_time_s=0.0, rate_rps=0.0,
    burst=(), ramp="call", constraint=None, transport="unary",
    backend="model", trace_ms=1000)


class FakeHttp:
    def __init__(self, replies):
        self.replies, self.seen = list(replies), []

    async def tool(self, name, arguments):
        self.seen.append(arguments)
        reply = self.replies.pop(0)
        if isinstance(reply, Exception):
            raise reply
        ids, finish = reply
        return {"tokenIds": ids, "finishReason": finish,
                "completionTokens": len(ids)}


def one(replies, new=10):
    load = client.Load(SCHED, seed=2**31 + 3, vocab=32000, host="h", port=1,
                       timeout_s=1.0)
    http = FakeHttp(replies)
    call = asyncio.run(load.one(http, 0, [5, 6, 7, 8], new, session=0))
    return call, http


def test_a_call_that_runs_to_length_is_one_request():
    call, http = one([([11] * 10, "length")])
    assert call.ok and call.completion_tokens == 10 and call.stops == 0
    assert call.segments == [[4, 14]] and len(http.seen) == 1


def test_an_early_stop_is_continued_to_the_scheduled_length():
    call, http = one([([11, 12, 13], "stop"), ([21] * 6, "length")])
    assert call.ok and call.stops == 1
    assert call.completion_tokens == 9  # the model made 9; one id is filler
    assert len(call.output) == 10 and call.output[:3] == [11, 12, 13]
    filler = call.output[3]
    assert filler >= schedule.FIRST_ID and call.output[4:] == [21] * 6
    assert call.segments == [[4, 7], [8, 14]]  # the filler is in no segment
    # the follow-up carries the whole history and asks for the rest
    second = http.seen[1]
    assert second["promptIds"]["intValues"] == [5, 6, 7, 8, 11, 12, 13, filler]
    assert second["maxNewTokens"] == 6


def test_a_stop_at_the_first_token_and_again():
    call, http = one([([], "stop"), ([31], "stop"), ([41] * 7, "length")])
    assert call.ok and call.stops == 2 and call.completion_tokens == 8
    assert len(call.output) == 10 and call.segments == [[4, 4], [5, 6], [7, 14]]


def test_a_stop_one_short_of_the_length_needs_no_follow_up():
    call, http = one([([11] * 9, "stop")])
    assert call.ok and call.stops == 0 and len(http.seen) == 1


def test_failures_are_failed_calls_not_exceptions():
    call, _ = one([RuntimeError("HTTP 429")])
    assert not call.ok and "429" in call.error
    call, _ = one([([1] * 11, "length")])  # more than asked for
    assert not call.ok and call.error.startswith("bad result")
    call, _ = one([([1] * 3, "timeout")])
    assert not call.ok
