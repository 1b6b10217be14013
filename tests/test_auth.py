"""odigosauth-analog token validation + tier enforcement at the CLI
(round-2 review item 6; reference: odigosauth/odigosauth.go:69)."""

import base64
import json
import time

import pytest

from odigos_tpu.utils.auth import (
    EXPECTED_ISSUER,
    EXPECTED_SUBJECT,
    TokenError,
    validate_tier_claim,
    validate_token,
)


def make_token(exp=None, iss=EXPECTED_ISSUER, sub=EXPECTED_SUBJECT,
               aud="onprem", drop=()):
    payload = {"exp": exp if exp is not None else time.time() + 3600,
               "iss": iss, "sub": sub, "aud": aud}
    for k in drop:
        payload.pop(k, None)
    body = base64.urlsafe_b64encode(
        json.dumps(payload).encode()).rstrip(b"=").decode()
    return f"eyJhbGciOiJub25lIn0.{body}.sig"


class TestValidateToken:
    def test_valid_token_returns_payload(self):
        payload = validate_token(make_token())
        assert payload["aud"] == "onprem"

    def test_aud_as_list(self):
        assert validate_token(make_token(aud=["cloud", "x"]))["aud"] == \
            ["cloud", "x"]

    @pytest.mark.parametrize("bad,match", [
        ("", "missing"),
        ("not-a-jwt", "format"),
        ("a.b", "format"),
        ("a.!!!.c", "decode"),
    ])
    def test_malformed(self, bad, match):
        with pytest.raises(TokenError, match=match):
            validate_token(bad)

    def test_expired_reports_duration(self):
        with pytest.raises(TokenError, match="expired for"):
            validate_token(make_token(exp=time.time() - 600))

    def test_wrong_claims(self):
        with pytest.raises(TokenError, match="invalid iss"):
            validate_token(make_token(iss="https://evil.example"))
        with pytest.raises(TokenError, match="invalid sub"):
            validate_token(make_token(sub="https://odigos.io/other"))
        with pytest.raises(TokenError, match="missing aud"):
            validate_token(make_token(drop=("aud",)))
        with pytest.raises(TokenError, match="missing exp"):
            validate_token(make_token(drop=("exp",)))

    def test_bool_exp_rejected(self):
        with pytest.raises(TokenError, match="invalid exp"):
            validate_token(make_token(exp=True))


class TestTierClaim:
    def test_onprem_token_entitles_both_paid_tiers(self):
        validate_tier_claim(make_token(aud="onprem"), "onprem")
        validate_tier_claim(make_token(aud="onprem"), "cloud")

    def test_cloud_token_does_not_entitle_onprem(self):
        with pytest.raises(TokenError, match="does not entitle"):
            validate_tier_claim(make_token(aud="cloud"), "onprem")


class TestCliEnforcement:
    def run_cli(self, tmp_path, *argv):
        from odigos_tpu.cli.commands import main

        return main(["--state-dir", str(tmp_path), *argv])

    def test_paid_tier_install_requires_token(self, tmp_path, capsys):
        assert self.run_cli(tmp_path, "install", "--tier", "onprem") == 1
        assert "pro token" in capsys.readouterr().err

    def test_paid_tier_install_with_token(self, tmp_path):
        assert self.run_cli(tmp_path, "install", "--tier", "onprem",
                            "--onprem-token", make_token()) == 0
        from odigos_tpu.cli.state import load_state

        assert load_state(str(tmp_path)).tier == "onprem"

    def test_profile_add_uses_installed_tier_not_flag(self, tmp_path,
                                                     capsys):
        """A community install cannot add a tier-gated profile by passing
        --tier onprem to `profile add` — entitlement was checked at
        install, not per-command."""
        assert self.run_cli(tmp_path, "install") == 0
        rc = self.run_cli(tmp_path, "profile", "add",
                          "--name", "java-ebpf-instrumentations",
                          "--tier", "onprem")
        assert rc == 1
        assert "tier-gated" in capsys.readouterr().err

    def test_onprem_install_can_add_gated_profile(self, tmp_path):
        assert self.run_cli(tmp_path, "install", "--tier", "onprem",
                            "--onprem-token", make_token()) == 0
        rc = self.run_cli(tmp_path, "profile", "add",
                          "--name", "java-ebpf-instrumentations")
        assert rc == 0
