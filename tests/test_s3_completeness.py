"""S3 completeness: tagging, per-action ACLs, streaming chunked SigV4,
post-policy uploads.

Counterparts: weed/s3api object tagging handlers, auth_credentials.go
identities/actions, chunked_reader_v4.go, and policy/post-policy.
"""

import asyncio
import base64
import hashlib
import hmac
import json
import time
import urllib.error
import urllib.parse
import urllib.request

import pytest

from cluster_util import Cluster, free_port
from seaweedfs_tpu.s3 import auth as auth_mod
from seaweedfs_tpu.s3.sigv4 import sign_request


@pytest.fixture(scope="module")
def cluster():
    c = Cluster(n_volume_servers=1, pulse=0.15)
    yield c
    c.shutdown()


def _boot_s3(cluster, **kwargs):
    from aiohttp import web

    from seaweedfs_tpu.s3.s3_server import S3Server

    filer = cluster.add_filer(chunk_size=16 * 1024)
    port = free_port()
    server = S3Server(filer.url, **kwargs)

    async def boot():
        runner = web.AppRunner(server.app)
        await runner.setup()
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        return runner

    cluster.runners.append(cluster.call(boot()))
    server.url = f"127.0.0.1:{port}"
    server._test_filer = filer
    return server


@pytest.fixture(scope="module")
def s3(cluster):
    return _boot_s3(cluster)


IDENTITIES = [
    {"name": "admin",
     "credentials": [{"accessKey": "ADMINKEY", "secretKey": "adminsecret"}],
     "actions": ["Admin"]},
    {"name": "reader",
     "credentials": [{"accessKey": "READKEY", "secretKey": "readsecret"}],
     "actions": ["Read", "List"]},
    {"name": "scoped",
     "credentials": [{"accessKey": "SCOPEKEY", "secretKey": "scopesecret"}],
     "actions": ["Write:onlythis"]},
]


@pytest.fixture(scope="module")
def s3_iam(cluster):
    return _boot_s3(cluster, iam=auth_mod.Iam(IDENTITIES))


def req(s3, method, path, data=None, headers=None):
    r = urllib.request.Request(f"http://{s3.url}{path}", data=data,
                               method=method, headers=headers or {})
    return urllib.request.urlopen(r, timeout=60)


def signed_req(s3, method, path, access, secret, data=b"", headers=None):
    url = f"http://{s3.url}{path}"
    hdrs = sign_request(method, url, headers or {}, data, access, secret)
    r = urllib.request.Request(url, data=data or None, method=method,
                               headers=hdrs)
    return urllib.request.urlopen(r, timeout=60)


# --- tagging ---

def test_object_tagging_crud(s3):
    req(s3, "PUT", "/tagbucket").read()
    req(s3, "PUT", "/tagbucket/obj.txt", data=b"hello").read()

    body = (b'<Tagging><TagSet>'
            b'<Tag><Key>env</Key><Value>prod</Value></Tag>'
            b'<Tag><Key>team</Key><Value>infra</Value></Tag>'
            b'</TagSet></Tagging>')
    with req(s3, "PUT", "/tagbucket/obj.txt?tagging", data=body) as r:
        assert r.status == 200
    with req(s3, "GET", "/tagbucket/obj.txt?tagging") as r:
        xml = r.read().decode()
    assert "<Key>env</Key>" in xml and "<Value>prod</Value>" in xml
    assert "<Key>team</Key>" in xml

    with req(s3, "DELETE", "/tagbucket/obj.txt?tagging") as r:
        assert r.status == 204
    with req(s3, "GET", "/tagbucket/obj.txt?tagging") as r:
        xml = r.read().decode()
    assert "<Tag>" not in xml


def test_put_object_with_tagging_header(s3):
    req(s3, "PUT", "/tagbucket/tagged.bin", data=b"x",
        headers={"x-amz-tagging": "a=1&b=2"}).read()
    with req(s3, "GET", "/tagbucket/tagged.bin?tagging") as r:
        xml = r.read().decode()
    assert "<Key>a</Key>" in xml and "<Value>2</Value>" in xml


# --- per-action ACLs ---

def test_acl_reader_cannot_write(s3_iam):
    signed_req(s3_iam, "PUT", "/aclbucket", "ADMINKEY",
               "adminsecret").read()
    signed_req(s3_iam, "PUT", "/aclbucket/w.txt", "ADMINKEY", "adminsecret",
               data=b"admin writes").read()
    # reader can read and list
    with signed_req(s3_iam, "GET", "/aclbucket/w.txt", "READKEY",
                    "readsecret") as r:
        assert r.read() == b"admin writes"
    with signed_req(s3_iam, "GET", "/aclbucket", "READKEY",
                    "readsecret") as r:
        assert b"w.txt" in r.read()
    # reader cannot write or create buckets
    with pytest.raises(urllib.error.HTTPError) as e:
        signed_req(s3_iam, "PUT", "/aclbucket/nope.txt", "READKEY",
                   "readsecret", data=b"no")
    assert e.value.code == 403
    with pytest.raises(urllib.error.HTTPError) as e:
        signed_req(s3_iam, "PUT", "/newbucket", "READKEY", "readsecret")
    assert e.value.code == 403


def test_acl_bucket_scoped_write(s3_iam):
    signed_req(s3_iam, "PUT", "/onlythis", "ADMINKEY", "adminsecret").read()
    signed_req(s3_iam, "PUT", "/other", "ADMINKEY", "adminsecret").read()
    signed_req(s3_iam, "PUT", "/onlythis/ok.txt", "SCOPEKEY", "scopesecret",
               data=b"scoped").read()
    with pytest.raises(urllib.error.HTTPError) as e:
        signed_req(s3_iam, "PUT", "/other/no.txt", "SCOPEKEY",
                   "scopesecret", data=b"denied")
    assert e.value.code == 403


def _presign(s3, method, path, access, secret, expires=900,
             amz_date=None):
    """Client-side presigned URL builder (the inverse of the server's
    _check_presigned; the math any SDK's generate_presigned_url does)."""
    import hashlib
    import hmac as hmac_mod
    import time
    import urllib.parse

    from seaweedfs_tpu.s3 import auth as auth_mod

    amz_date = amz_date or time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())
    date = amz_date[:8]
    scope = f"{date}/us-east-1/s3/aws4_request"
    q = {
        "X-Amz-Algorithm": "AWS4-HMAC-SHA256",
        "X-Amz-Credential": f"{access}/{scope}",
        "X-Amz-Date": amz_date,
        "X-Amz-Expires": str(expires),
        "X-Amz-SignedHeaders": "host",
    }
    cq = "&".join(
        f"{urllib.parse.quote(k, safe='-_.~')}="
        f"{urllib.parse.quote(v, safe='-_.~')}"
        for k, v in sorted(q.items()))
    canonical = "\n".join([
        method, urllib.parse.quote(path, safe="/-_.~"), cq,
        f"host:{s3.url}\n", "host", "UNSIGNED-PAYLOAD"])
    sts = "\n".join(["AWS4-HMAC-SHA256", amz_date, scope,
                     hashlib.sha256(canonical.encode()).hexdigest()])
    k = auth_mod.signing_key(secret, date, "us-east-1", "s3")
    sig = hmac_mod.new(k, sts.encode(), hashlib.sha256).hexdigest()
    return f"http://{s3.url}{path}?{cq}&X-Amz-Signature={sig}"


def test_presigned_url_get_and_put(s3_iam):
    """Presigned query-string SigV4 (doesPresignedSignatureMatch,
    weed/s3api/auth_signature_v4.go): no Authorization header needed."""
    signed_req(s3_iam, "PUT", "/presignb", "ADMINKEY", "adminsecret")
    signed_req(s3_iam, "PUT", "/presignb/doc.txt", "ADMINKEY",
               "adminsecret", data=b"presigned payload").read()

    # GET via presigned URL, plain urlopen — no auth header
    url = _presign(s3_iam, "GET", "/presignb/doc.txt", "READKEY",
                   "readsecret")
    with urllib.request.urlopen(url, timeout=30) as r:
        assert r.read() == b"presigned payload"

    # PUT via presigned URL with a write-capable identity
    url = _presign(s3_iam, "PUT", "/presignb/up.txt", "ADMINKEY",
                   "adminsecret")
    req_obj = urllib.request.Request(url, data=b"uploaded", method="PUT")
    urllib.request.urlopen(req_obj, timeout=30).read()
    with signed_req(s3_iam, "GET", "/presignb/up.txt", "ADMINKEY",
                    "adminsecret") as r:
        assert r.read() == b"uploaded"

    # tampered signature is rejected
    bad = url[:-4] + "beef"
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(urllib.request.Request(
            bad, data=b"x", method="PUT"), timeout=30)
    assert e.value.code == 403

    # expired URL is rejected
    import time
    old = time.strftime("%Y%m%dT%H%M%SZ", time.gmtime(time.time() - 4000))
    url = _presign(s3_iam, "GET", "/presignb/doc.txt", "READKEY",
                   "readsecret", expires=60, amz_date=old)
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(url, timeout=30)
    assert e.value.code == 403

    # ACL still applies through presigned auth
    url = _presign(s3_iam, "PUT", "/presignb/deny.txt", "READKEY",
                   "readsecret")
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(urllib.request.Request(
            url, data=b"x", method="PUT"), timeout=30)
    assert e.value.code == 403


# --- streaming chunked SigV4 ---

class _FakeStream:
    def __init__(self, data: bytes):
        self._data = data
        self._pos = 0

    async def read(self, n: int) -> bytes:
        out = self._data[self._pos:self._pos + n]
        self._pos += len(out)
        return out

    async def readexactly(self, n: int) -> bytes:
        out = self._data[self._pos:self._pos + n]
        if len(out) != n:
            raise asyncio.IncompleteReadError(out, n)
        self._pos += n
        return out


def _frame_chunks(payload: bytes, chunk_size: int, key: bytes,
                  seed: str, amz_date: str, scope: str) -> bytes:
    out = bytearray()
    prev = seed
    pieces = [payload[i:i + chunk_size]
              for i in range(0, len(payload), chunk_size)] + [b""]
    for piece in pieces:
        sts = "\n".join(["AWS4-HMAC-SHA256-PAYLOAD", amz_date, scope, prev,
                         hashlib.sha256(b"").hexdigest(),
                         hashlib.sha256(piece).hexdigest()])
        sig = hmac.new(key, sts.encode(), hashlib.sha256).hexdigest()
        out += f"{len(piece):x};chunk-signature={sig}\r\n".encode()
        out += piece + b"\r\n"
        prev = sig
    return bytes(out)


def test_chunked_sigv4_decode_and_verify():
    key = auth_mod.signing_key("secret", "20260730", "us-east-1")
    payload = bytes(range(256)) * 40
    framed = _frame_chunks(payload, 1000, key, "seedsig",
                           "20260730T000000Z",
                           "20260730/us-east-1/s3/aws4_request")
    got = asyncio.run(auth_mod.read_chunked_sigv4(
        _FakeStream(framed), "seedsig", key, "20260730T000000Z",
        "20260730/us-east-1/s3/aws4_request"))
    assert got == payload

    # a tampered chunk fails signature verification
    bad = bytearray(framed)
    bad[len(bad) // 2] ^= 0xFF
    with pytest.raises(auth_mod.ChunkedSigV4Error):
        asyncio.run(auth_mod.read_chunked_sigv4(
            _FakeStream(bytes(bad)), "seedsig", key, "20260730T000000Z",
            "20260730/us-east-1/s3/aws4_request"))

    # unverified mode still de-frames
    got = asyncio.run(auth_mod.read_chunked_sigv4(_FakeStream(framed)))
    assert got == payload


def test_chunked_sigv4_end_to_end(s3):
    req(s3, "PUT", "/chunkbucket").read()
    payload = b"streamed-" * 1000
    framed = bytearray()
    for piece in (payload[:4096], payload[4096:], b""):
        framed += f"{len(piece):x};chunk-signature=deadbeef\r\n".encode()
        framed += piece + b"\r\n"
    req(s3, "PUT", "/chunkbucket/streamed.bin", data=bytes(framed),
        headers={"x-amz-content-sha256":
                 "STREAMING-AWS4-HMAC-SHA256-PAYLOAD"}).read()
    with req(s3, "GET", "/chunkbucket/streamed.bin") as r:
        assert r.read() == payload


# --- post-policy upload ---

def _policy_doc(bucket: str, expires_in: float = 600.0) -> str:
    exp = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                        time.gmtime(time.time() + expires_in))
    return base64.b64encode(json.dumps({
        "expiration": exp,
        "conditions": [{"bucket": bucket},
                       ["starts-with", "$key", "uploads/"]],
    }).encode()).decode()


def _post_policy_body(fields: dict, file_data: bytes,
                      boundary: str) -> bytes:
    out = bytearray()
    for k, v in fields.items():
        out += (f"--{boundary}\r\nContent-Disposition: form-data; "
                f'name="{k}"\r\n\r\n{v}\r\n').encode()
    out += (f"--{boundary}\r\nContent-Disposition: form-data; "
            f'name="file"; filename="f.bin"\r\n'
            f"Content-Type: application/octet-stream\r\n\r\n").encode()
    out += file_data + f"\r\n--{boundary}--\r\n".encode()
    return bytes(out)


def test_post_policy_upload(s3_iam):
    signed_req(s3_iam, "PUT", "/postbucket", "ADMINKEY",
               "adminsecret").read()
    policy = _policy_doc("postbucket")
    date = time.strftime("%Y%m%d", time.gmtime())
    cred = f"ADMINKEY/{date}/us-east-1/s3/aws4_request"
    key = auth_mod.signing_key("adminsecret", date, "us-east-1")
    sig = hmac.new(key, policy.encode(), hashlib.sha256).hexdigest()
    fields = {"key": "uploads/${filename}", "policy": policy,
              "x-amz-credential": cred, "x-amz-signature": sig,
              "x-amz-date": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())}
    body = _post_policy_body(fields, b"posted bytes", "bnd123")
    with req(s3_iam, "POST", "/postbucket", data=body,
             headers={"Content-Type":
                      "multipart/form-data; boundary=bnd123"}) as r:
        assert r.status == 204
    with signed_req(s3_iam, "GET", "/postbucket/uploads/f.bin", "ADMINKEY",
                    "adminsecret") as r:
        assert r.read() == b"posted bytes"

    # a broken signature is rejected
    fields["x-amz-signature"] = "0" * 64
    body = _post_policy_body(fields, b"nope", "bnd123")
    with pytest.raises(urllib.error.HTTPError) as e:
        req(s3_iam, "POST", "/postbucket", data=body,
            headers={"Content-Type":
                     "multipart/form-data; boundary=bnd123"})
    assert e.value.code == 403

    # a policy violating its own key condition is rejected
    policy2 = _policy_doc("postbucket")
    sig2 = hmac.new(key, policy2.encode(), hashlib.sha256).hexdigest()
    fields2 = {"key": "elsewhere/x.bin", "policy": policy2,
               "x-amz-credential": cred, "x-amz-signature": sig2,
               "x-amz-date": fields["x-amz-date"]}
    body = _post_policy_body(fields2, b"nope", "bnd123")
    with pytest.raises(urllib.error.HTTPError) as e:
        req(s3_iam, "POST", "/postbucket", data=body,
            headers={"Content-Type":
                     "multipart/form-data; boundary=bnd123"})
    assert e.value.code == 403


def test_post_policy_content_length_range(s3_iam):
    """A signed content-length-range condition bounds the payload size
    (weed/s3api/policy/post-policy.go) — only the upload handler can
    enforce it, since only it sees the actual bytes."""
    signed_req(s3_iam, "PUT", "/clrbucket", "ADMINKEY", "adminsecret").read()
    exp = time.strftime("%Y-%m-%dT%H:%M:%SZ",
                        time.gmtime(time.time() + 600))
    policy = base64.b64encode(json.dumps({
        "expiration": exp,
        "conditions": [{"bucket": "clrbucket"},
                       ["starts-with", "$key", "uploads/"],
                       ["content-length-range", 4, 16]],
    }).encode()).decode()
    date = time.strftime("%Y%m%d", time.gmtime())
    cred = f"ADMINKEY/{date}/us-east-1/s3/aws4_request"
    key = auth_mod.signing_key("adminsecret", date, "us-east-1")
    sig = hmac.new(key, policy.encode(), hashlib.sha256).hexdigest()
    fields = {"key": "uploads/${filename}", "policy": policy,
              "x-amz-credential": cred, "x-amz-signature": sig,
              "x-amz-date": time.strftime("%Y%m%dT%H%M%SZ", time.gmtime())}
    hdrs = {"Content-Type": "multipart/form-data; boundary=bnd123"}

    # in range: accepted
    body = _post_policy_body(fields, b"12345678", "bnd123")
    with req(s3_iam, "POST", "/clrbucket", data=body, headers=hdrs) as r:
        assert r.status == 204

    # too large: EntityTooLarge
    body = _post_policy_body(fields, b"x" * 17, "bnd123")
    with pytest.raises(urllib.error.HTTPError) as e:
        req(s3_iam, "POST", "/clrbucket", data=body, headers=hdrs)
    assert e.value.code == 400
    assert b"EntityTooLarge" in e.value.read()

    # too small: EntityTooSmall
    body = _post_policy_body(fields, b"ab", "bnd123")
    with pytest.raises(urllib.error.HTTPError) as e:
        req(s3_iam, "POST", "/clrbucket", data=body, headers=hdrs)
    assert e.value.code == 400
    assert b"EntityTooSmall" in e.value.read()


def test_multipart_with_manifested_part(cluster, s3):
    """A part large enough to be chunk-manifested must assemble with
    correct offsets (the filer flattens it at complete time)."""
    # find the filer behind this s3 server and shrink its manifest batch
    filer = s3._test_filer
    old_batch = filer.manifest_batch
    filer.manifest_batch = 3
    try:
        req(s3, "PUT", "/mpbucket").read()
        with req(s3, "POST", "/mpbucket/big.bin?uploads") as r:
            body = r.read().decode()
        upload_id = body.split("<UploadId>")[1].split("</UploadId>")[0]
        # part 1: spans many chunks (chunk_size is 16KB in the fixture)
        part1 = bytes([7]) * (16 * 1024 * 5)   # 5 chunks > batch of 3
        part2 = bytes([9]) * (16 * 1024 * 2)
        req(s3, "PUT",
            f"/mpbucket/big.bin?partNumber=1&uploadId={upload_id}",
            data=part1).read()
        req(s3, "PUT",
            f"/mpbucket/big.bin?partNumber=2&uploadId={upload_id}",
            data=part2).read()
        with req(s3, "POST", f"/mpbucket/big.bin?uploadId={upload_id}",
                 data=b"<CompleteMultipartUpload/>") as r:
            assert b"CompleteMultipartUploadResult" in r.read()
        with req(s3, "GET", "/mpbucket/big.bin") as r:
            got = r.read()
        assert got == part1 + part2
    finally:
        filer.manifest_batch = old_batch


def test_list_v2_start_after_and_encoding(s3):
    req(s3, "PUT", "/lv2bucket").read()
    for k in ("a.txt", "b c.txt", "d.txt"):
        req(s3, "PUT", f"/lv2bucket/{urllib.parse.quote(k)}",
            data=b"x").read()
    with req(s3, "GET", "/lv2bucket?list-type=2&start-after=a.txt") as r:
        xml = r.read().decode()
    assert "<Key>a.txt</Key>" not in xml
    assert "<Key>b c.txt</Key>" in xml and "<Key>d.txt</Key>" in xml
    with req(s3, "GET",
             "/lv2bucket?list-type=2&encoding-type=url") as r:
        xml = r.read().decode()
    assert "<EncodingType>url</EncodingType>" in xml
    assert "<Key>b%20c.txt</Key>" in xml


def test_shell_repl_smoke(cluster, s3):
    """The interactive REPL accepts piped commands and emits JSON lines."""
    import subprocess
    import sys
    env = dict(__import__("os").environ, JAX_PLATFORMS="cpu")
    repo = __import__("os").path.dirname(
        __import__("os").path.dirname(__import__("os").path.abspath(
            __file__)))
    env["PYTHONPATH"] = ":".join(
        p for p in (env.get("PYTHONPATH", ""), repo) if p)
    master = cluster.master_url.split(",")[0]
    out = subprocess.run(
        [sys.executable, "-m", "seaweedfs_tpu.cli", "shell",
         "-server", master],
        input="volume.list\nhelp\nexit\n", text=True,
        capture_output=True, env=env, timeout=60)
    assert out.returncode == 0, out.stderr[-500:]
    assert '"nodes"' in out.stdout
    assert "volume.balance" in out.stdout
