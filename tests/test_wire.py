"""Wire protocol round-trip tests (reference analog: the FlatBuffers
encode/decode paths in horovod/common/message.cc:122-215,317-346)."""

import pytest

from horovod_tpu.common.message import (
    DataType, Request, RequestList, RequestType, Response, ResponseList,
    ResponseType,
)
from horovod_tpu.common import wire


def test_request_roundtrip():
    req = Request(request_rank=3, request_type=RequestType.ALLREDUCE,
                  tensor_type=DataType.FLOAT32, tensor_name="grad/conv1",
                  root_rank=-1, device=2, tensor_shape=(32, 64, 3),
                  prescale_factor=0.5, postscale_factor=2.0)
    rl = RequestList([req], shutdown=False)
    out = wire.parse_request_list(wire.serialize_request_list(rl))
    assert out == rl
    assert out.requests[0].tensor_shape == (32, 64, 3)


def test_request_list_shutdown_bit():
    rl = RequestList([], shutdown=True)
    out = wire.parse_request_list(wire.serialize_request_list(rl))
    assert out.shutdown is True
    assert out.requests == []


def test_many_requests_roundtrip():
    reqs = [
        Request(request_rank=r, request_type=t, tensor_type=dt,
                tensor_name=f"t{r}.{int(t)}.{int(dt)}",
                tensor_shape=(r + 1, 7), root_rank=r % 2, device=-1)
        for r in range(5)
        for t in (RequestType.ALLREDUCE, RequestType.ALLGATHER,
                  RequestType.BROADCAST)
        for dt in (DataType.FLOAT32, DataType.BFLOAT16, DataType.INT64)
    ]
    rl = RequestList(reqs)
    out = wire.parse_request_list(wire.serialize_request_list(rl))
    assert out == rl


def test_response_roundtrip():
    resp = Response(response_type=ResponseType.ALLREDUCE,
                    tensor_names=["a", "b", "c"],
                    devices=[-1, -1], tensor_sizes=[12, 4, 9],
                    prescale_factor=1.0, postscale_factor=0.25)
    rl = ResponseList([resp], shutdown=False)
    out = wire.parse_response_list(wire.serialize_response_list(rl))
    assert out == rl


@pytest.mark.parametrize("code", [4, 255])
def test_unknown_algorithm_code_is_refused(code):
    """A response stamped with an algorithm code this build does not
    define (4 was the retired mesh plane's) is a protocol error, in a
    plain and in a cached-cycle frame alike: parsed, it would be
    routed as ALG_DEFAULT here and as something else by its sender."""
    from horovod_tpu.common import wire_dtype as wd
    from horovod_tpu.common.message import CacheCycleResponse
    assert code not in wd.ALG_NAMES
    resp = Response(response_type=ResponseType.ALLREDUCE,
                    tensor_names=["a"], devices=[-1], tensor_sizes=[4],
                    algorithm=code)
    rl = ResponseList([resp])
    with pytest.raises(ConnectionError, match=f"algorithm code {code}"):
        wire.parse_response_list(wire.serialize_response_list(rl))
    cached = CacheCycleResponse(epoch=1, nslots=2, grant_mask=0,
                                invalid_mask=0, response_list=rl)
    with pytest.raises(ConnectionError, match=f"algorithm code {code}"):
        wire.parse_cycle_response(wire.serialize_cycle_response(cached))
    for known in wd.ALG_NAMES:
        resp.algorithm = known
        assert wire.parse_response_list(
            wire.serialize_response_list(rl)) == rl


def test_error_response_roundtrip():
    resp = Response(response_type=ResponseType.ERROR,
                    tensor_names=["bad"],
                    error_message="Mismatched allreduce tensor shapes: ...")
    rl = ResponseList([resp], shutdown=True)
    out = wire.parse_response_list(wire.serialize_response_list(rl))
    assert out.shutdown
    assert out.responses[0].response_type == ResponseType.ERROR
    assert "Mismatched" in out.responses[0].error_message


def test_unicode_tensor_names():
    req = Request(tensor_name="层/グラデーション∇", tensor_shape=(1,))
    rl = RequestList([req])
    out = wire.parse_request_list(wire.serialize_request_list(rl))
    assert out.requests[0].tensor_name == "层/グラデーション∇"


def test_randomized_roundtrips():
    """Seeded fuzz over the codec: arbitrary ranks/dtypes/shapes/
    scales/unicode names must survive serialize -> parse exactly."""
    import numpy as np
    from horovod_tpu.common.message import (
        DataType, Request, RequestList, RequestType, Response,
        ResponseList, ResponseType,
    )
    from horovod_tpu.common import wire

    rng = np.random.RandomState(7)
    req_types = [RequestType.ALLREDUCE, RequestType.ALLGATHER,
                 RequestType.BROADCAST, RequestType.ALLTOALL,
                 RequestType.REDUCESCATTER, RequestType.BARRIER]
    dtypes = list(DataType)
    names = ["t", "grad/層/0", "a.b-c_d", "🙂/émoji", "x" * 200]
    for _ in range(60):
        reqs = [Request(
            request_rank=int(rng.randint(0, 1 << 20)),
            request_type=req_types[rng.randint(len(req_types))],
            tensor_type=dtypes[rng.randint(len(dtypes))],
            tensor_name=names[rng.randint(len(names))]
            + str(rng.randint(1000)),
            root_rank=int(rng.randint(-1, 64)),
            device=int(rng.randint(-1, 8)),
            tensor_shape=[int(s) for s in
                          rng.randint(0, 1 << 16,
                                      size=rng.randint(0, 6))],
            prescale_factor=float(rng.randn()),
            postscale_factor=float(rng.randn()),
        ) for _ in range(rng.randint(0, 8))]
        rl = RequestList(reqs, shutdown=bool(rng.randint(2)))
        assert wire.parse_request_list(
            wire.serialize_request_list(rl)) == rl

        resps = [Response(
            response_type=ResponseType(
                [ResponseType.ALLREDUCE, ResponseType.ALLGATHER,
                 ResponseType.BROADCAST, ResponseType.ERROR][
                     rng.randint(4)]),
            tensor_names=[f"n{j}.{rng.randint(100)}"
                          for j in range(rng.randint(0, 5))],
            error_message="e" * rng.randint(0, 50),
            devices=[int(d) for d in
                     rng.randint(0, 8, size=rng.randint(0, 4))],
            tensor_sizes=[int(s) for s in
                          rng.randint(0, 1 << 30,
                                      size=rng.randint(0, 4))],
            prescale_factor=float(rng.randn()),
            postscale_factor=float(rng.randn()),
        ) for _ in range(rng.randint(0, 5))]
        rsl = ResponseList(resps, shutdown=bool(rng.randint(2)),
                           tuned_cycle_time_ms=float(abs(rng.randn())),
                           tuned_fusion_threshold_bytes=int(
                               rng.randint(0, 1 << 26)))
        assert wire.parse_response_list(
            wire.serialize_response_list(rsl)) == rsl
