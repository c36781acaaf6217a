"""The command tree (reference cmd/root.go:46-66)."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Optional

import click

from keto_tpu.cmd import client as client_pkg
from keto_tpu.relationtuple.model import RelationTuple
from keto_tpu.version import __version__


def _print_formatted(obj, fmt: str, default_str: Optional[str] = None) -> None:
    if fmt == "json":
        click.echo(json.dumps(obj))
    elif fmt == "json-pretty":
        click.echo(json.dumps(obj, indent=2))
    else:
        click.echo(default_str if default_str is not None else json.dumps(obj, indent=2))


_format_flag = click.option(
    "--format",
    "fmt",
    type=click.Choice(["default", "json", "json-pretty"]),
    default="default",
    help="output format",
)
_read_remote_flag = click.option(
    "--read-remote", default=None, help="read API gRPC remote (env KETO_READ_REMOTE)"
)
_write_remote_flag = click.option(
    "--write-remote", default=None, help="write API gRPC remote (env KETO_WRITE_REMOTE)"
)


@click.group()
@click.version_option(version=__version__, prog_name="keto-tpu")
def cli():
    """keto-tpu — a TPU-native Zanzibar-style permission server."""


# -- serve -------------------------------------------------------------------


@cli.command()
@click.option("--config", "-c", default=None, help="path to the config file")
def serve(config):
    """Start the read and write API servers (REST + gRPC multiplexed).

    Reference: cmd/server/serve.go:33-70.
    """
    from keto_tpu.config.provider import Config
    from keto_tpu.driver.daemon import Daemon
    from keto_tpu.driver.registry import Registry
    from keto_tpu.x import profiling

    cfg = Config(config_file=config)
    profiling.attach(cfg.get("profiling", ""))  # reference main.go:25-28
    registry = Registry(cfg, use_default_compile_cache=True)
    daemon = Daemon(registry)
    # SIGTERM/SIGINT → drain in-flight requests (serve.drain_timeout_s)
    # behind a NOT_SERVING readiness flip, then exit — rolling restarts
    # drop zero accepted requests
    daemon.install_signal_handlers()
    daemon.serve_all(block=True)


# -- check / expand ----------------------------------------------------------


@cli.command()
@click.argument("subject")
@click.argument("relation")
@click.argument("namespace")
@click.argument("object")
@_read_remote_flag
@_format_flag
def check(subject, relation, namespace, object, read_remote, fmt):
    """Check whether a subject has a relation on an object.

    Argument order matches the reference: <subject> <relation> <namespace>
    <object> (reference cmd/check/root.go:25-61).
    """
    from ory.keto.acl.v1alpha1 import acl_pb2, check_service_pb2

    if "#" in subject:
        from keto_tpu.relationtuple.model import subject_from_string
        from keto_tpu.relationtuple.proto_codec import subject_to_proto

        sub = subject_to_proto(subject_from_string(subject))
    else:
        sub = acl_pb2.Subject(id=subject)

    with client_pkg.conn(client_pkg.read_remote(read_remote)) as ch:
        resp = client_pkg.unary(
            ch,
            "/ory.keto.acl.v1alpha1.CheckService/Check",
            check_service_pb2.CheckRequest(
                namespace=namespace, object=object, relation=relation, subject=sub
            ),
            check_service_pb2.CheckResponse,
        )
    _print_formatted(
        {"allowed": resp.allowed}, fmt, "Allowed" if resp.allowed else "Denied"
    )
    if not resp.allowed and fmt == "default":
        sys.exit(0)


@cli.command()
@click.argument("relation")
@click.argument("namespace")
@click.argument("object")
@click.option("--max-depth", "-d", default=100, help="maximum depth of the tree")
@_read_remote_flag
@_format_flag
def expand(relation, namespace, object, max_depth, read_remote, fmt):
    """Expand a subject set into a tree of subjects.

    Argument order matches the reference: <relation> <namespace> <object>
    (reference cmd/expand/root.go:18-76).
    """
    from ory.keto.acl.v1alpha1 import acl_pb2, expand_service_pb2

    from keto_tpu.expand.proto_codec import tree_from_proto

    with client_pkg.conn(client_pkg.read_remote(read_remote)) as ch:
        resp = client_pkg.unary(
            ch,
            "/ory.keto.acl.v1alpha1.ExpandService/Expand",
            expand_service_pb2.ExpandRequest(
                subject=acl_pb2.Subject(
                    set=acl_pb2.SubjectSet(
                        namespace=namespace, object=object, relation=relation
                    )
                ),
                max_depth=max_depth,
            ),
            expand_service_pb2.ExpandResponse,
        )
    tree = tree_from_proto(resp.tree if resp.HasField("tree") else None)
    if tree is None:
        if fmt == "default":
            click.echo(
                "Got an empty tree. This probably means that the requested relation "
                "tuple is not present in Keto."
            )
        else:
            click.echo("null")
        return
    _print_formatted(tree.to_json(), fmt, str(tree))


# -- relation-tuple ----------------------------------------------------------


@cli.group("relation-tuple")
def relation_tuple():
    """Read and manipulate relation tuples."""


def _parse_tuple_files(files) -> list[RelationTuple]:
    """Human-syntax tuple files: one ``ns:obj#rel@subject`` per line,
    ``//`` comments and blank lines ignored (reference
    cmd/relationtuple/parse.go:48-91)."""
    rts = []
    for fn in files:
        text = sys.stdin.read() if fn == "-" else Path(fn).read_text()
        name = "stdin" if fn == "-" else fn
        for i, row in enumerate(text.split("\n")):
            row = row.strip()
            if not row or row.startswith("//"):
                continue
            try:
                rts.append(RelationTuple.from_string(row))
            except Exception as e:
                raise SystemExit(f"Could not decode {name}:{i+1}\n  {row}\n\n{e}")
    return rts


def _collect_tuple_jsons(files) -> list[RelationTuple]:
    """JSON tuple files / directories / stdin (reference
    cmd/relationtuple/create.go:20-96)."""
    rts = []

    def parse_blob(raw: str, name: str):
        try:
            data = json.loads(raw)
        except json.JSONDecodeError as e:
            raise SystemExit(f"Could not decode {name}: {e}")
        items = data if isinstance(data, list) else [data]
        for item in items:
            item.pop("$schema", None)
            rts.append(RelationTuple.from_json(item))

    for fn in files:
        if fn == "-":
            parse_blob(sys.stdin.read(), "stdin")
            continue
        p = Path(fn)
        if p.is_dir():
            for child in sorted(p.rglob("*.json")):
                parse_blob(child.read_text(), str(child))
        else:
            parse_blob(p.read_text(), str(p))
    return rts


_TABLE_HEADER = ("NAMESPACE", "OBJECT ID", "RELATION NAME", "SUBJECT")


def _print_tuple_table(rts: list[RelationTuple]) -> None:
    rows = [(rt.namespace, rt.object, rt.relation, str(rt.subject)) for rt in rts]
    widths = [
        max(len(_TABLE_HEADER[i]), *(len(r[i]) for r in rows)) if rows else len(_TABLE_HEADER[i])
        for i in range(4)
    ]
    for row in (_TABLE_HEADER, *rows):
        click.echo("\t".join(c.ljust(w) for c, w in zip(row, widths)).rstrip())


@relation_tuple.command()
@click.argument("files", nargs=-1, required=True)
@_format_flag
def parse(files, fmt):
    """Parse human readable relation tuples (``//`` comments ignored)."""
    rts = _parse_tuple_files(files)
    if fmt in ("json", "json-pretty"):
        payload = rts[0].to_json() if len(rts) == 1 else [rt.to_json() for rt in rts]
        _print_formatted(payload, fmt)
    elif len(rts) == 1:
        click.echo(str(rts[0]))
    else:
        _print_tuple_table(rts)


@relation_tuple.command()
@click.argument("files", nargs=-1, required=True)
@_write_remote_flag
def create(files, write_remote):
    """Create relation tuples from JSON files, directories, or stdin."""
    _transact(files, "INSERT", write_remote)


@relation_tuple.command()
@click.argument("files", nargs=-1, required=True)
@_write_remote_flag
def delete(files, write_remote):
    """Delete relation tuples defined in JSON files, directories, or stdin."""
    _transact(files, "DELETE", write_remote)


def _transact(files, action: str, write_remote_flag):
    from ory.keto.acl.v1alpha1 import write_service_pb2

    from keto_tpu.relationtuple.proto_codec import tuple_to_proto

    rts = _collect_tuple_jsons(files)
    deltas = [
        write_service_pb2.RelationTupleDelta(
            action=getattr(write_service_pb2.RelationTupleDelta, action),
            relation_tuple=tuple_to_proto(rt),
        )
        for rt in rts
    ]
    with client_pkg.conn(client_pkg.write_remote(write_remote_flag)) as ch:
        client_pkg.unary(
            ch,
            "/ory.keto.acl.v1alpha1.WriteService/TransactRelationTuples",
            write_service_pb2.TransactRelationTuplesRequest(relation_tuple_deltas=deltas),
            write_service_pb2.TransactRelationTuplesResponse,
        )
    word = "created" if action == "INSERT" else "deleted"
    click.echo(f"Successfully {word} {len(rts)} relation tuples.")


@relation_tuple.command()
@click.argument("namespace")
@click.option("--object", default="", help="object filter")
@click.option("--relation", default="", help="relation filter")
@click.option("--subject-id", default=None, help="subject id filter")
@click.option("--subject-set", default=None, help='subject set filter ("ns:obj#rel")')
@click.option("--page-size", default=100, help="maximum number of items to return")
@click.option("--page-token", default="", help="page token from a previous response")
@_read_remote_flag
@_format_flag
def get(namespace, object, relation, subject_id, subject_set, page_size, page_token, read_remote, fmt):
    """Get relation tuples matching the given partial tuple (paginated)."""
    from ory.keto.acl.v1alpha1 import acl_pb2, read_service_pb2

    from keto_tpu.relationtuple.proto_codec import tuple_from_proto

    query = read_service_pb2.ListRelationTuplesRequest.Query(
        namespace=namespace, object=object, relation=relation
    )
    if subject_id is not None and subject_set is not None:
        raise SystemExit("at most one of --subject-id / --subject-set may be used")
    if subject_id is not None:
        query.subject.CopyFrom(acl_pb2.Subject(id=subject_id))
    elif subject_set is not None:
        ns, _, rest = subject_set.partition(":")
        obj, _, rel = rest.partition("#")
        query.subject.CopyFrom(
            acl_pb2.Subject(set=acl_pb2.SubjectSet(namespace=ns, object=obj, relation=rel))
        )

    with client_pkg.conn(client_pkg.read_remote(read_remote)) as ch:
        resp = client_pkg.unary(
            ch,
            "/ory.keto.acl.v1alpha1.ReadService/ListRelationTuples",
            read_service_pb2.ListRelationTuplesRequest(
                query=query, page_size=page_size, page_token=page_token
            ),
            read_service_pb2.ListRelationTuplesResponse,
        )
    rts = [tuple_from_proto(t) for t in resp.relation_tuples]
    if fmt in ("json", "json-pretty"):
        _print_formatted(
            {
                "relation_tuples": [rt.to_json() for rt in rts],
                "next_page_token": resp.next_page_token,
            },
            fmt,
        )
    else:
        _print_tuple_table(rts)
        if resp.next_page_token:
            click.echo(f"\nNEXT PAGE TOKEN\t{resp.next_page_token}")
        else:
            click.echo("\nIS LAST PAGE\ttrue")


# -- namespace ---------------------------------------------------------------


@cli.group()
def namespace():
    """Work with namespace definitions."""


@namespace.command()
@click.argument("files", nargs=-1, required=True)
def validate(files):
    """Validate namespace definition files against the JSON schema
    (reference cmd/namespace/validate.go:20-58), and the userset rewrites
    they carry (``config.relations``) as the server does when it loads them."""
    from keto_tpu import namespace as namespace_pkg
    from keto_tpu.config.provider import parse_namespace_file

    failed = False
    for fn in files:
        try:
            nss = parse_namespace_file(Path(fn))
            namespace_pkg.MemoryManager(nss)  # raises SchemaError, naming the relation
            for ns in nss:
                click.echo(f"{fn}: namespace {ns.name!r} (id {ns.id}) is valid")
        except Exception as e:
            click.echo(f"{fn}: INVALID — {e}", err=True)
            failed = True
    if failed:
        sys.exit(1)


@namespace.command("migrate-legacy")
@click.argument("target", required=False)
@click.option("--config", "-c", default=None)
@click.option("--yes", "-y", is_flag=True)
def migrate_legacy(target, config, yes):
    """Migrate v0.6-era per-namespace tables into the single tuple table
    (reference cmd/namespace/migrate_legacy.go:18-118)."""
    from keto_tpu.persistence.legacy import ToSingleTableMigrator

    p = _migrator(config)
    p.migrate_up()
    m = ToSingleTableMigrator(p)
    namespaces = m.legacy_namespaces()
    if target is not None:
        namespaces = [n for n in namespaces if n.name == target]
        if not namespaces:
            raise SystemExit(f"no legacy table found for namespace {target!r}")
    if not namespaces:
        click.echo("No legacy namespace tables found, nothing to do.")
        return
    names = ", ".join(n.name for n in namespaces)
    if not yes and not click.confirm(f"Migrate legacy tables for: {names}?"):
        raise SystemExit("aborted")
    for ns in namespaces:
        report = m.migrate_namespace(ns)
        click.echo(f"{ns.name}: migrated {report.migrated[ns.name]} tuples")
        for bad in report.invalid:
            click.echo(f"  SKIPPED {bad.object}#{bad.relation}@{bad.subject!r}: {bad.error}", err=True)


# -- migrate -----------------------------------------------------------------


@cli.group()
def migrate():
    """Run or inspect storage migrations (reference cmd/migrate/*.go)."""


def _migrator(config):
    from keto_tpu.config.provider import Config

    cfg = Config(config_file=config)
    dsn = cfg.dsn
    if dsn.startswith("sqlite://"):
        from keto_tpu.persistence.sqlite import SQLitePersister

        return SQLitePersister(dsn, cfg.namespace_manager, auto_migrate=False)
    if dsn.startswith(("postgres://", "postgresql://", "cockroach://")):
        from keto_tpu.persistence.postgres import PostgresPersister

        return PostgresPersister(dsn, cfg.namespace_manager, auto_migrate=False)
    raise SystemExit(f"migrations apply to SQL DSNs (sqlite/postgres); got {dsn!r}")


@migrate.command()
@click.option("--config", "-c", default=None)
@click.option("--yes", "-y", is_flag=True, help="do not ask for confirmation")
def up(config, yes):
    """Apply pending migrations."""
    p = _migrator(config)
    pending = [m for m, applied in p.migration_status() if not applied]
    if not pending:
        click.echo("Migrations already applied, nothing to do.")
        return
    if not yes and not click.confirm(f"Apply {len(pending)} migrations?"):
        raise SystemExit("aborted")
    p.migrate_up()
    click.echo(f"Successfully applied {len(pending)} migrations.")


@migrate.command()
@click.option("--config", "-c", default=None)
@click.option("--yes", "-y", is_flag=True)
@click.option("--steps", default=1, help="how many migrations to roll back")
def down(config, yes, steps):
    """Roll back the latest migrations."""
    p = _migrator(config)
    if not yes and not click.confirm(f"Roll back {steps} migrations?"):
        raise SystemExit("aborted")
    n = p.migrate_down(steps)
    click.echo(f"Successfully rolled back {n} migrations.")


@migrate.command()
@click.option("--config", "-c", default=None)
def status(config):
    """Show the migration status."""
    p = _migrator(config)
    click.echo("VERSION\tSTATUS")
    for m, applied in p.migration_status():
        click.echo(f"{m}\t{'applied' if applied else 'pending'}")


# -- status / version --------------------------------------------------------


@cli.command("status")
@click.option("--block", is_flag=True, help="wait until the server is healthy")
@_read_remote_flag
@_write_remote_flag
@click.option("--write", is_flag=True, help="probe the write API instead of the read API")
def status_cmd(block, read_remote, write_remote, write):
    """Query the gRPC health endpoint (reference cmd/status/root.go:22-117)."""
    from grpchealth.v1 import health_pb2

    import grpc

    target = (
        client_pkg.write_remote(write_remote) if write else client_pkg.read_remote(read_remote)
    )
    while True:
        try:
            with client_pkg.conn(target) as ch:
                resp = client_pkg.unary(
                    ch,
                    "/grpc.health.v1.Health/Check",
                    health_pb2.HealthCheckRequest(),
                    health_pb2.HealthCheckResponse,
                )
            if resp.status == health_pb2.HealthCheckResponse.SERVING:
                click.echo("SERVING")
                return
        # a raw RpcError (server up but unhealthy / mid-start) must keep the
        # --block watch alive, same as the dial failures surfaced as
        # SystemExit (reference cmd/status/root.go:67-100 retries both)
        except SystemExit:
            if not block:
                raise
        except grpc.RpcError:
            if not block:
                click.echo("NOT_SERVING")
                raise SystemExit(1)
        if not block:
            click.echo("NOT_SERVING")
            sys.exit(1)
        time.sleep(1)


@cli.command()
def version():
    """Print the framework version."""
    click.echo(__version__)


def main():
    cli(prog_name="keto-tpu")


if __name__ == "__main__":
    main()
