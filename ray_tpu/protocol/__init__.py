"""Wire schema (protobuf) for the control plane.

``raytpu.proto`` is the source of truth and ``raytpu_pb2.py``, checked
in beside it, is the product: nothing is generated at import, because a
copied or checked-out tree has arbitrary file times and an import must
not depend on them.  After editing the .proto, regenerate by hand:

    cd ray_tpu/protocol && protoc --python_out=. raytpu.proto

Parity: src/ray/protobuf/*.proto compiled into ray._raylet /
ray.core.generated at build time.
"""

from __future__ import annotations

from ray_tpu.protocol import raytpu_pb2 as pb

Frame = pb.Frame
ObjectMeta = pb.ObjectMeta
JoinRequest = pb.JoinRequest
JoinReply = pb.JoinReply

__all__ = ["pb", "Frame", "ObjectMeta", "JoinRequest", "JoinReply"]
