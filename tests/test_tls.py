"""TLS/mTLS envelope (weed/security/tls.go role).

A master runs with [tls] configured in security.toml (verify_client=true):
every surface must reject plaintext and cert-less clients, and accept a
client presenting a CA-signed certificate — on both the HTTP port and the
gRPC port.
"""

import json
import os
import socket
import ssl
import subprocess
import sys
import time
import urllib.request

import pytest

from cluster_util import free_port_with_grpc_twin


def _gen_certs(d: str) -> dict:
    """Self-signed CA + server/client certs via the openssl CLI."""
    def run(*args):
        subprocess.run(args, check=True, capture_output=True, cwd=d)

    run("openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
        "-keyout", "ca.key", "-out", "ca.crt", "-days", "2",
        "-subj", "/CN=test-ca")
    for name, cn in (("server", "127.0.0.1"), ("client", "test-client")):
        run("openssl", "req", "-newkey", "rsa:2048", "-nodes",
            "-keyout", f"{name}.key", "-out", f"{name}.csr",
            "-subj", f"/CN={cn}")
        ext = os.path.join(d, f"{name}.ext")
        with open(ext, "w") as f:
            f.write("subjectAltName=IP:127.0.0.1,DNS:localhost\n")
        run("openssl", "x509", "-req", "-in", f"{name}.csr",
            "-CA", "ca.crt", "-CAkey", "ca.key", "-CAcreateserial",
            "-out", f"{name}.crt", "-days", "2", "-extfile", ext)
    return {k: os.path.join(d, k) for k in
            ("ca.crt", "server.crt", "server.key",
             "client.crt", "client.key")}


@pytest.fixture(scope="module")
def tls_master(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tls"))
    certs = _gen_certs(d)
    with open(os.path.join(d, "security.toml"), "w") as f:
        f.write(f"""
[tls]
ca_file = "{certs['ca.crt']}"
cert_file = "{certs['server.crt']}"
key_file = "{certs['server.key']}"
verify_client = true
https = true
""")
    port = free_port_with_grpc_twin()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.Popen(
        [sys.executable, "-m", "seaweedfs_tpu.cli", "master",
         "-port", str(port), "-mdir", d],
        cwd=d, env=env, stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL)
    # readiness: TLS handshake with the client cert succeeds
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.load_verify_locations(certs["ca.crt"])
    ctx.check_hostname = False
    ctx.load_cert_chain(certs["client.crt"], certs["client.key"])
    deadline = time.time() + 20
    while True:
        try:
            with socket.create_connection(("127.0.0.1", port), 1) as s:
                with ctx.wrap_socket(s) as tls_s:
                    break
        except OSError:
            if time.time() > deadline:
                proc.kill()
                raise
            time.sleep(0.3)
    yield {"port": port, "certs": certs, "ctx": ctx}
    proc.terminate()
    try:
        proc.wait(timeout=5)
    except subprocess.TimeoutExpired:
        proc.kill()


def test_https_with_client_cert_works(tls_master):
    opener = urllib.request.build_opener(
        urllib.request.HTTPSHandler(context=tls_master["ctx"]))
    body = json.loads(opener.open(
        f"https://127.0.0.1:{tls_master['port']}/cluster/status",
        timeout=10).read())
    assert body.get("is_leader") is True


def test_plaintext_http_rejected(tls_master):
    with pytest.raises(Exception):
        urllib.request.urlopen(
            f"http://127.0.0.1:{tls_master['port']}/cluster/status",
            timeout=5)


def test_certless_tls_client_rejected(tls_master):
    # trusts the CA but presents NO client certificate: mTLS must refuse
    ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
    ctx.load_verify_locations(tls_master["certs"]["ca.crt"])
    ctx.check_hostname = False
    opener = urllib.request.build_opener(
        urllib.request.HTTPSHandler(context=ctx))
    with pytest.raises(Exception):
        opener.open(
            f"https://127.0.0.1:{tls_master['port']}/cluster/status",
            timeout=5).read()


def test_grpc_secure_channel_works(tls_master):
    import grpc

    from seaweedfs_tpu.pb import master_pb2 as mpb
    from seaweedfs_tpu.pb.rpc import MasterStub
    certs = tls_master["certs"]
    creds = grpc.ssl_channel_credentials(
        root_certificates=open(certs["ca.crt"], "rb").read(),
        private_key=open(certs["client.key"], "rb").read(),
        certificate_chain=open(certs["client.crt"], "rb").read())
    ch = grpc.secure_channel(f"127.0.0.1:{tls_master['port'] + 10000}",
                             creds)
    stub = MasterStub(ch)
    resp = stub.GetMasterConfiguration(
        mpb.GetMasterConfigurationRequest(), timeout=10)
    assert resp.volume_size_limit_mb > 0
    ch.close()


def test_grpc_insecure_channel_rejected(tls_master):
    import grpc

    from seaweedfs_tpu.pb import master_pb2 as mpb
    from seaweedfs_tpu.pb.rpc import MasterStub
    ch = grpc.insecure_channel(f"127.0.0.1:{tls_master['port'] + 10000}")
    stub = MasterStub(ch)
    with pytest.raises(grpc.RpcError):
        stub.GetMasterConfiguration(mpb.GetMasterConfigurationRequest(),
                                    timeout=5)
    ch.close()
